"""Frozen predict artifact — the immutable deployable of a finished fit.

``FitResult.predict`` used to re-derive everything a query needs (value
panels, masked centroid norms, landmark norms) from the live training
objects on every call. ``freeze(result)`` does that derivation ONCE and
packs the outcome into a single immutable pytree:

  * the feature-map tables — RFF frequencies + phases, Nystrom landmarks,
    count-sketch hash/sign, TensorSketch hash/sign stacks — optionally
    stored at the bf16 tile dtype (``kernels/precision.py``; accumulation
    stays f32 in every consumer, signs store int8 under bf16);
  * the centroids in embedded space and their MASKED squared norms
    (empty clusters carry +BIG so they are never assigned — baked in at
    freeze time, not recomputed per request);
  * the value panel ``v = proj @ centroids.T`` for Nystrom (the per-call
    matmul ``embed_panels`` used to pay is gone) / ``centroids.T``
    otherwise;
  * for ``method="exact"`` fits: the global medoids + their kernel
    diagonal and the KernelSpec scalars.

The artifact is exactly what ``kernels.ops.predict_assign`` consumes —
the serving engine (``repro.serving.assign``) AOT-compiles one program
per shape bucket over these arrays and nothing else. Its resident bytes
are priced by ``core.memory.serve_footprint_bytes`` (reported by
``artifact_nbytes`` next to the analytic price in the serve benchmark).

Save/load round-trips through one ``.npz`` (arrays; bf16 tiles stored as
their exact f32 lift — bf16 -> f32 -> bf16 is lossless — and re-rounded
at load) plus a JSON member for the static scalars, so a pod-scale fit
ships to a serving host as one file.
"""
from __future__ import annotations

import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

#: artifact kinds (== MiniBatchConfig.method values).
KINDS = ("rff", "nystrom", "sketch", "tensorsketch", "exact")

#: kinds the fused Pallas predict kernel serves (ops.predict_assign
#: fused=True). TensorSketch (FFT conv) and exact (medoid Gram row) run
#: their documented jnp programs instead — still one program per bucket.
FUSED_KINDS = ("rff", "nystrom", "sketch")


@dataclasses.dataclass(frozen=True)
class FrozenArtifact:
    """Immutable predict artifact: arrays + hashable statics.

    ``arrays`` maps name -> device array (the pytree leaves); ``statics``
    holds the compile-time scalars (map_kind/gamma/coef0/degree/scale/
    m/c/d ...) that bake into the bucket programs. ``precision`` is the
    tile dtype the map tables were frozen at ("f32" | "bf16").
    """

    kind: str
    precision: str
    arrays: dict
    statics: dict

    @property
    def n_clusters(self) -> int:
        return int(self.statics["c"])

    @property
    def in_dim(self) -> int:
        return int(self.statics["d"])

    @property
    def dim(self) -> int:
        """Embedded dim m (== C for exact: one medoid Gram column each)."""
        return int(self.statics.get("m", self.statics["c"]))

    def feature_map(self):
        """Rebuild the O(nnz) sketch map for CSR ingestion (sketch kinds).

        Signs may be stored int8 (bf16 policy) — lifted back to f32 here;
        ±1 is exact in every format so the rebuilt map is bit-identical.
        """
        if self.kind == "sketch":
            from repro.approx.sketch import CountSketchMap
            return CountSketchMap(
                h=self.arrays["h"],
                sign=self.arrays["sign"].astype(jnp.float32),
                m=int(self.statics["m"]))
        if self.kind == "tensorsketch":
            from repro.approx.sketch import TensorSketchMap
            return TensorSketchMap(
                hs=self.arrays["hs"],
                signs=self.arrays["signs"].astype(jnp.float32),
                m=int(self.statics["m"]),
                degree=int(self.statics["degree"]),
                gamma=float(self.statics["gamma"]),
                coef0=float(self.statics["coef0"]))
        raise ValueError(
            f"kind {self.kind!r} has no O(nnz) sketch map; CSR requests "
            "are densified at ingestion (repro.serving.assign)")

    def kernel_spec(self):
        """The KernelSpec of an exact-kind artifact."""
        from repro.core.kernels import KernelSpec
        if self.kind != "exact":
            raise ValueError(f"kind {self.kind!r} carries no KernelSpec")
        s = self.statics
        return KernelSpec(name=s["kernel"], gamma=float(s["gamma"]),
                          coef0=float(s["coef0"]), degree=int(s["degree"]))


def _flatten(a: FrozenArtifact):
    keys = tuple(sorted(a.arrays))
    leaves = tuple(a.arrays[k] for k in keys)
    aux = (a.kind, a.precision, keys, tuple(sorted(a.statics.items())))
    return leaves, aux


def _unflatten(aux, leaves) -> FrozenArtifact:
    kind, precision, keys, statics = aux
    return FrozenArtifact(kind=kind, precision=precision,
                          arrays=dict(zip(keys, leaves)),
                          statics=dict(statics))


jax.tree_util.register_pytree_node(FrozenArtifact, _flatten, _unflatten)


def _panels(centroids: Array, counts: Array):
    """f32 centroids, transposed value panel, and MASKED squared norms."""
    from repro.kernels.ops import _masked_csq
    c32, csq = _masked_csq(centroids, counts)
    return c32, c32.T, csq


def freeze_map(fmap, centroids: Array, counts: Array, *,
               precision: str = "f32") -> FrozenArtifact:
    """Freeze an embedded-space model (feature map + centroids).

    The artifact-build half of ``freeze`` that needs no ``FitResult`` —
    the audit CLI and tests build serving programs from synthetic parts
    through this. ``precision`` stores the map TILES (frequencies /
    landmarks / signs) at the policy dtype; panels and norms stay f32
    (they are accumulator-side values, never tiles).
    """
    from repro.approx.nystrom import NystromMap
    from repro.approx.rff import RFFMap
    from repro.approx.sketch import CountSketchMap, TensorSketchMap
    from repro.kernels.precision import resolve_precision

    p = resolve_precision(precision)
    counts = jnp.asarray(counts, jnp.float32)
    c32, v, csq = _panels(jnp.asarray(centroids), counts)
    c, m = c32.shape
    common = dict(c=c, m=m)

    if isinstance(fmap, RFFMap):
        w = p.cast_tiles(fmap.w)
        arrays = dict(w=w, aux=fmap.b.astype(jnp.float32)[:, None],
                      v=v, csq=csq, centroids=c32, counts=counts)
        statics = dict(map_kind="rff", gamma=1.0, coef0=1.0, degree=1,
                       scale=float(fmap.scale), d=int(fmap.in_dim), **common)
        return FrozenArtifact("rff", precision, arrays, statics)
    if isinstance(fmap, NystromMap):
        w = p.cast_tiles(fmap.landmarks)
        # norms of the CAST landmarks: the Mercer epilogue's norm/dot terms
        # must cancel exactly the way the tile-dtype kernel computes them.
        aux = jnp.sum(w.astype(jnp.float32) ** 2, axis=1, keepdims=True)
        spec = fmap.spec
        arrays = dict(w=w, aux=aux,
                      v=fmap.proj.astype(jnp.float32) @ c32.T,
                      csq=csq, centroids=c32, counts=counts)
        statics = dict(map_kind=spec.name, gamma=float(spec.gamma),
                       coef0=float(spec.coef0), degree=int(spec.degree),
                       scale=1.0, d=int(fmap.in_dim), **common)
        return FrozenArtifact("nystrom", precision, arrays, statics)
    if isinstance(fmap, CountSketchMap):
        arrays = dict(h=fmap.h.astype(jnp.int32),
                      sign=fmap.sign.astype(p.sign_dtype),
                      v=v, csq=csq, centroids=c32, counts=counts)
        statics = dict(map_kind="sketch", d=int(fmap.in_dim), **common)
        return FrozenArtifact("sketch", precision, arrays, statics)
    if isinstance(fmap, TensorSketchMap):
        arrays = dict(hs=fmap.hs.astype(jnp.int32),
                      signs=fmap.signs.astype(p.sign_dtype),
                      v=v, csq=csq, centroids=c32, counts=counts)
        statics = dict(map_kind="tensorsketch", degree=int(fmap.degree),
                       gamma=float(fmap.gamma), coef0=float(fmap.coef0),
                       d=int(fmap.in_dim), **common)
        return FrozenArtifact("tensorsketch", precision, arrays, statics)
    raise TypeError(f"unsupported feature map {type(fmap).__name__}")


def freeze(result, *, precision: str = "f32") -> FrozenArtifact:
    """``FitResult`` -> ``FrozenArtifact`` (the deployable predict model).

    f32 artifacts predict bit-identically to the fit-time path;
    ``precision="bf16"`` halves the map-table bytes with the bounded NMI
    drift the precision tests pin (tile rounding only — every consumer
    still accumulates f32).
    """
    # a mesh fit leaves its state on every device of the mesh; the
    # artifact is served from one (a Mosaic kernel cannot be partitioned
    # by XLA), so it is built from a host copy
    state, fmap = jax.tree.map(jnp.asarray, jax.device_get(
        (result.state, result.fmap)))
    if fmap is not None:
        return freeze_map(fmap, state.centroids, state.cardinalities,
                          precision=precision)
    if result.spec is None:
        raise ValueError(
            "cannot freeze an exact-path FitResult without its KernelSpec "
            "(FitResult.spec) — prediction would use the wrong kernel")
    c, d = state.medoids.shape
    arrays = dict(medoids=jnp.asarray(state.medoids, jnp.float32),
                  medoid_diag=jnp.asarray(state.medoid_diag, jnp.float32))
    statics = dict(kernel=result.spec.name, gamma=float(result.spec.gamma),
                   coef0=float(result.spec.coef0),
                   degree=int(result.spec.degree), c=int(c), d=int(d))
    return FrozenArtifact("exact", precision, arrays, statics)


def artifact_nbytes(art: FrozenArtifact) -> int:
    """Resident bytes of the artifact's arrays (the measured counterpart
    of ``core.memory.serve_footprint_bytes`` at bucket=0)."""
    return int(sum(np.asarray(a).nbytes for a in art.arrays.values()))


def save_artifact(art: FrozenArtifact, path: str) -> str:
    """Write one ``.npz``: arrays + a JSON member with kind/precision/
    statics/dtypes. bf16 tiles are stored as their exact f32 lift
    (bf16 -> f32 is lossless) and re-rounded at load."""
    arrays, dtypes = {}, {}
    for k, a in art.arrays.items():
        a = np.asarray(a)
        dtypes[k] = str(a.dtype)
        if a.dtype.name == "bfloat16":
            a = a.astype(np.float32)
        arrays[k] = a
    meta = json.dumps({"kind": art.kind, "precision": art.precision,
                       "statics": art.statics, "dtypes": dtypes})
    buf = io.BytesIO()
    np.savez(buf, __meta__=np.frombuffer(meta.encode(), np.uint8), **arrays)
    with open(path, "wb") as fh:
        fh.write(buf.getvalue())
    return path


def load_artifact(path: str) -> FrozenArtifact:
    """Read a ``save_artifact`` file back into device arrays."""
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        arrays = {}
        for k, dt in meta["dtypes"].items():
            arrays[k] = jnp.asarray(z[k]).astype(dt)
    if meta["kind"] not in KINDS:
        raise ValueError(f"unknown artifact kind {meta['kind']!r} in {path}")
    return FrozenArtifact(kind=meta["kind"], precision=meta["precision"],
                          arrays=arrays, statics=meta["statics"])
