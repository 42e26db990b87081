"""Distributed inner GD loop — the paper's Alg.1 on a JAX device mesh,
restructured as an s-step communication-avoiding iteration.

Faithful mapping (1-D, paper §3.3): mini-batch rows are sharded over the data
axes; every device owns its rows of K^i, f and its slice of U. The paper's
two collectives (line 10 allgather U, line 13 allreduce g) are packed into
exactly ONE allgather + ONE psum per global sync:

    allgather: the new labels U
    psum:      one flat [C + 2] buffer over the row axes — the g partials
               with the local cost and convergence count appended (counts
               and f are local once U is gathered; g's row reduction,
               the cost sum and the changed flag all share the one psum)

The kernel block never crosses the network (computed and consumed
shard-locally), matching the paper's communication bound of
Q*(N/(B*P) + 2C) bytes per iteration.

Beyond-paper 2-D extension (DESIGN.md §2): the landmark (column) dimension
is additionally sharded over the ``model`` axis; per-device kernel-block
memory drops from rows_p x |L| to rows_p x |L|/M. Here the landmark-ROW
block K_ll is replicated over the row axes ([|L|, |L|/M] per device
instead of a D-way row shard — the planner prices the growth,
``core.memory.engine_footprint_bytes``), which makes g local-over-rows
after the label gather so counts/f/g share ONE flat [rows_p + 2, C] psum
over the model axis, while the cost/changed scalars ride the label
allgather bit-packed into the same int32 buffer. Still exactly
1 allgather + 1 psum per sync. Setting mesh model axis = 1 recovers the
faithful algorithm exactly.

s-step mode (``DistributedInnerConfig.s_step = s``, after the
communication-avoiding kernel k-means of Bellavita et al., PAPERS.md): each
while-loop body runs one globally-consistent assignment plus s-1 LOCAL
Lloyd refinements against the replicated landmark stats — each refinement
scatters this shard's fresh labels into its carried estimate of the global
label vector and re-derives the stats as (frozen remote partials + fresh
local partials) — before the single fused sync. The collective bill per
Lloyd iteration is therefore (1 allgather + 1 psum) / s.

2-D replica consistency under s-step: the refinements are column-local, so
model-axis replicas of the same row block refine against DIFFERENT stat
estimates (each owns a different landmark-column slice) and their labels
legitimately diverge between syncs. The sync therefore widens the label
allgather to the model axis and takes model shard 0's labels/cost/changed
as THE canonical refinement — every replica leaves each sync with
identical labels, the model-axis stats psum reduces partials of one
consistent label vector, and the replication promised by the row-only
out_specs holds. The algorithm is exactly "refine with model shard 0's
column freshness", deterministic whatever M; 1-D needs none of this (row
shards own disjoint rows — no replicas to disagree).

Communication bill per SYNC (one sync per while-loop body; divide by s for
the per-Lloyd-iteration bill; D = row-shard count, M = model-axis size,
rows_p = N/(B*D), C clusters, 4-byte scalars):

==============  =====================  ===================================
mesh layout     collectives per sync   payload bytes per sync (per device)
==============  =====================  ===================================
1-D (data)      1 allgather + 1 psum   allgather 4*N/B (labels);
                                       psum 4*(C + 2) (g + cost + changed)
2-D (+model)    1 allgather + 1 psum   allgather 4*(N/B + 2*D) (labels
                                       + packed cost/changed; x M when
                                       s > 1 — the canonicalizing gather
                                       spans the model axis too);
                                       psum 4*C*(rows_p + 2)
                                       (f block + counts + g, one flat
                                       concat over the model axis)
==============  =====================  ===================================

There is NO fixpoint epilogue: the loop body is pipelined — it assigns from
the stats the previous sync produced, then syncs the stats of the labels it
just wrote — so at exit the carried stats already describe the final
labels. The one collective pair outside the loop is the PROLOGUE sync that
seeds the carry from u0, so the audited outside-the-loop bill is also
exactly {allgather: 1, psum: 1} (``launch.audit`` proves both statically).

Cost semantics at exit: the returned cost is the one synced WITH the final
labels — each row's min-distance measured against the stats of the
PREVIOUS sync (the stats the assignment argmin'd over). On converged exits
this equals the cost of the final labels under their own stats (the labels
did not change, so the previous sync's stats are theirs); when the loop is
cut off by ``max_iters`` it is the pipelined, one-sync-stale cost — NOT
recomputed against the final stats, which would cost an extra epilogue
psum and break the audited outside-the-loop bill.

WHERE the per-device Gram blocks live is the ``GramEngine`` contract
(repro.core.engine) — the same engine, and literally the same stats code
(``engine_stats_raw``/``finalize_stats``), as the single-host loop; this
module only adds the fused collectives (one batched ``ReducePlan`` instead
of per-quantity psum hooks). Per device and per inner iteration
(rows_p = N/(B*D), L_m = |L|/M):

=============  =======================  ==================  ================
engine mode    peak HBM                 Gram FLOPs          when it wins
=============  =======================  ==================  ================
materialize    rows_p*L_m + rows_p*C    0 (built once per   many inner
               (K resident + f; 2-D     batch, amortized)   iterations
               adds the replicated
               |L|*L_m K_ll block)
fused          rows_p*C (f only; K      rows_p*L_m*d +      HBM-bound, few
               tiles live in VMEM,      |L|*L_m*d rebuilt   iterations, TPU
               Pallas; jnp fallback     every iteration     (Pallas path)
               recomputes per iter)
tiled          2*bm*L_m + rows_p*C      same rebuild as     full block
               (double-buffered row     fused               exceeds HBM;
               panels, portable jnp)                        s = 1 survives
=============  =======================  ==================  ================

materialize reads the resident block once per iteration (O(L_m) bytes/row);
fused raises arithmetic intensity to ~L_m FLOPs/byte by rebuilding the tile
in VMEM (O(d + C) bytes/row); tiled pays fused's FLOP bill at HBM-panel
granularity so it runs on any backend — its panels are double-buffered
(engine ``double_buffer``) so the panel build overlaps the contraction and
any in-flight collective. The planner (``repro.core.memory.plan``) prices
all three against the memory budget and names the pick as ``Plan.engine``;
``benchmarks/roofline.py`` and ``benchmarks/fig6_scaling.py`` measure the
trade.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (GramEngine, ReducePlan, assign_from_stats,
                               engine_stats_raw, finalize_stats,
                               resolve_engine)
from repro.core.kernels import KernelSpec

from .compat import shard_map

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DistributedInnerConfig:
    n_clusters: int
    kernel: KernelSpec = KernelSpec("rbf", gamma=1.0)
    max_iters: int = 100
    # Gram residency: "materialize" | "fused" | "tiled" or a GramEngine.
    engine: object = "materialize"
    # tile-dtype policy (repro.kernels.precision): "f32" | "bf16". Applied
    # through the engine: feature shards and (under materialize) resident
    # Gram blocks move as bf16 tiles, all accumulation and every collective
    # payload (counts/f/g partials) stays f32 — reduction order across the
    # mesh never meets rounded operands.
    precision: str = "f32"
    row_axes: tuple[str, ...] = ("data",)
    col_axis: str | None = "model"   # None -> faithful 1-D distribution
    # communication-avoiding depth: Lloyd refinements per global sync.
    # s_step=1 is the fully-synchronous loop (bit-identical labels to the
    # pre-s-step engine); s_step>1 trades s-1 locally-stale refinements
    # for 1/s of the collective bill.
    s_step: int = 1

    def __post_init__(self):
        if self.s_step < 1:
            raise ValueError(f"s_step must be >= 1, got {self.s_step}")
        resolve_engine(self.engine, self.precision)   # validates both


class DistInnerResult(NamedTuple):
    labels: Array      # [n] int32, row-sharded
    f: Array           # [n, C] f32, row-sharded
    g: Array           # [C] replicated
    counts: Array      # [C] replicated
    n_iter: Array
    cost: Array


def _body_factory(cfg: DistributedInnerConfig, x_local, lm_cols, lm_rows,
                  diag_local, l_idx_cols, l_idx_rows, wgt_local,
                  n_local_rows: int, row_strides: tuple[int, ...],
                  d_size: int, m_size: int):
    """Builds the while_loop body, cond, and carry init/unpack for one
    device's shard. ``row_strides``/``d_size``/``m_size`` linearize this
    device's position along the row axes and size the model axis (static,
    from the mesh shape)."""
    spec = cfg.kernel
    row_axes, col_axis = cfg.row_axes, cfg.col_axis
    C = cfg.n_clusters
    s = cfg.s_step
    engine = resolve_engine(cfg.engine, cfg.precision)
    two_d = col_axis is not None

    # per-batch Gram operators (paper lines 3 & 11-12 precompute): the
    # materialize engine evaluates and keeps the blocks here; fused/tiled
    # only record the features and rebuild tiles/panels inside each
    # iteration's matvec. 1-D: lm_rows is this shard's row slice (as in
    # the paper); 2-D: lm_rows is the FULL landmark set (replicated over
    # the row axes) so g needs no row reduction of its own.
    op_xl = engine.prepare(spec, x_local, lm_cols)        # rows_p x L/M
    op_ll = engine.prepare(spec, lm_rows, lm_cols)        # (L/D | L) x L/M

    def local_stats(u_full):
        """Raw per-shard partials of the gathered labels: counts/f are
        local totals in 1-D and model-axis partials in 2-D; g is a
        row-axes partial in 1-D and a model-axis partial in 2-D."""
        return engine_stats_raw(
            engine, spec, op_xl, op_ll,
            jnp.take(u_full, l_idx_cols), jnp.take(u_full, l_idx_rows), C)

    # the mesh's ONE stats collective, handed to the SHARED engine
    # contract as a batched ReducePlan (2-D): counts/f/g ride a single
    # flat [rows_p + 2, C] psum over the model axis. 1-D needs no stats
    # psum beyond g, which shares the scalar psum inside sync().
    if two_d:
        def _fused_psum(counts_p, f_p, g_p):
            with jax.named_scope("obs:psum_fused"):
                flat = jnp.concatenate(
                    [f_p, counts_p[None, :], g_p[None, :]], axis=0)
                flat = jax.lax.psum(flat, col_axis)
            return flat[-2], flat[:-2], flat[-1]
        reduce_plan = ReducePlan(_fused_psum)

    if s > 1:
        # this shard's row-block offset in the global label vector (for
        # scattering refined labels into the carried u_full estimate, and
        # for slicing the canonical labels back out after a 2-D sync).
        row_off = jnp.int32(0)
        for a, stride in zip(row_axes, row_strides):
            row_off = row_off + jax.lax.axis_index(a) * stride
        row_off = row_off * n_local_rows

    def sync(u_local, cost_loc, changed_loc):
        """THE global sync: exactly 1 allgather + 1 psum, whatever the
        layout. Returns (u_loc, u_full, totals, locals, cost, changed)
        with u_loc this shard's canonical labels (== ``u_local`` except
        in 2-D s-step mode, see below), u_full the canonical global label
        vector and totals/locals its raw (un-normalized) stats payload."""
        if not two_d:
            # 1-D: gather labels; ONE [C + 2] psum over the row axes
            # carries the g partials plus the cost/changed scalars —
            # counts and f are already local totals. (No canonicalization
            # needed at any s: row shards own DISJOINT rows, so there are
            # no replicas whose refinements could disagree.)
            with jax.named_scope("obs:allgather_u"):
                u_full = jax.lax.all_gather(u_local, row_axes, tiled=True)
            counts_p, f_p, g_p = local_stats(u_full)
            with jax.named_scope("obs:psum_fused"):
                flat = jnp.concatenate([
                    g_p, jnp.stack([cost_loc,
                                    changed_loc.astype(jnp.float32)])])
                flat = jax.lax.psum(flat, row_axes)
            locs = (counts_p, f_p, g_p)
            totals = (counts_p, f_p, flat[:-2])
            return (u_local, u_full, totals, locs, flat[-2],
                    flat[-1].astype(jnp.int32))
        # 2-D: the cost/changed scalars ride the label gather (bitcast
        # into the same int32 buffer) so the row-axes reduction costs no
        # extra collective; counts/f/g then share one flat psum over the
        # model axis.
        packed = jnp.concatenate([
            u_local,
            jax.lax.bitcast_convert_type(cost_loc[None], jnp.int32),
            changed_loc[None]])
        if s > 1:
            # s-step refinements are collective-free and column-LOCAL, so
            # model-axis replicas of the same row block legitimately
            # arrive here with DIFFERENT refined labels (each refined
            # against its own landmark-column slice of the stats). The
            # label gather is widened to the model axis and model shard
            # 0's labels/cost/changed are taken as THE canonical
            # refinement, so every replica leaves the sync with identical
            # labels and the model-axis stats psum below reduces partials
            # of one consistent label vector — restoring the replication
            # the out_specs promise. Still exactly 1 allgather + 1 psum;
            # the gather payload grows by the model-axis factor M.
            with jax.named_scope("obs:allgather_u"):
                buf = jax.lax.all_gather(
                    packed, row_axes + (col_axis,), tiled=True)
            buf = buf.reshape(d_size, m_size, n_local_rows + 2)[:, 0]
        else:
            with jax.named_scope("obs:allgather_u"):
                buf = jax.lax.all_gather(packed, row_axes, tiled=True)
            buf = buf.reshape(d_size, n_local_rows + 2)
        u_full = buf[:, :n_local_rows].reshape(-1)
        cost = jnp.sum(jax.lax.bitcast_convert_type(
            buf[:, n_local_rows], jnp.float32))
        changed = jnp.sum(buf[:, n_local_rows + 1])
        u_loc = (jax.lax.dynamic_slice(u_full, (row_off,), (n_local_rows,))
                 if s > 1 else u_local)
        locs = local_stats(u_full)
        totals = reduce_plan(*locs)
        return u_loc, u_full, totals, locs, cost, changed

    def _rem(totals, locs):
        """Frozen remote contribution = reduced totals - own partials.
        1-D: counts/f are local totals (remote = 0, kept scalar); only g
        has a cross-shard remainder."""
        if two_d:
            return tuple(t - l for t, l in zip(totals, locs))
        return (jnp.float32(0), jnp.float32(0), totals[2] - locs[2])

    def body(state):
        if s > 1:
            u, u_full, totals, rem, t, _, _ = state
        else:
            u, totals, t, _, _ = state
        # pipelined assignment: argmin against the stats the LAST sync
        # produced (for the first body, the prologue's stats of u0) —
        # the same labels the pre-s-step loop produced at this t.
        f, g, counts = finalize_stats(*totals)
        u_new, mind = assign_from_stats(f, g, counts)
        if s > 1:
            for _ in range(s - 1):
                # local refinement: scatter our fresh labels into the
                # carried global estimate, re-derive stats as frozen
                # remote + fresh local partials — no collectives.
                u_full = jax.lax.dynamic_update_slice(
                    u_full, u_new, (row_off,))
                locs = local_stats(u_full)
                est = tuple(r + l for r, l in zip(rem, locs))
                f, g, counts = finalize_stats(*est)
                u_new, mind = assign_from_stats(f, g, counts)
        changed_loc = jnp.sum((u_new != u).astype(jnp.int32))
        # ghost rows (wgt 0) replicate real rows to divide the mesh; they
        # follow their source row's label but must not inflate the cost.
        cost_loc = jnp.sum(
            wgt_local * (diag_local.astype(jnp.float32) + mind))
        u2, u_full2, totals2, locs2, cost2, changed2 = sync(
            u_new, cost_loc, changed_loc)
        if s > 1:
            return (u2, u_full2, totals2, _rem(totals2, locs2),
                    t + 1, cost2, changed2 > 0)
        return u2, totals2, t + 1, cost2, changed2 > 0

    def cond(state):
        changed, t = state[-1], state[-3]
        return jnp.logical_and(changed, t < cfg.max_iters)

    def init(u0_local):
        # PROLOGUE sync: seed the carry with the stats of u0 (dummy
        # cost/changed — overwritten by the first body's sync). This is
        # the only collective pair outside the while loop.
        u0 = u0_local.astype(jnp.int32)
        u0c, u_full0, totals0, locs0, _, _ = sync(
            u0, jnp.float32(0.0), jnp.int32(0))
        t0 = jnp.array(0, jnp.int32)
        cost0 = jnp.array(jnp.inf, jnp.float32)
        if s > 1:
            return (u0c, u_full0, totals0, _rem(totals0, locs0),
                    t0, cost0, jnp.array(True))
        return u0c, totals0, t0, cost0, jnp.array(True)

    def unpack(state):
        if s > 1:
            u, _, totals, _, t, cost, _ = state
        else:
            u, totals, t, cost, _ = state
        return u, totals, t, cost

    return body, cond, init, unpack


def collectives_per_iteration(cfg: DistributedInnerConfig,
                              n_local_rows: int | None = None) -> dict:
    """Analytic per-SYNC collective bill of the inner while_loop body —
    the jit-safe way to count them: the traced program is static, so the
    flight recorder multiplies these constants by the returned ``n_iter``
    instead of instrumenting inside the loop (which would change the
    lowered program). One sync per body; with ``cfg.s_step = s`` a body
    covers s Lloyd refinements, so the per-Lloyd-iteration bill is this
    divided by s. Returns ``{"allgather": 1, "psum": 1, "psum_bytes":
    ...}`` — the fused-payload contract ``launch.audit`` proves
    statically. ``psum_bytes`` is the per-device psum payload: the flat
    [C + 2] g/cost/changed buffer in 1-D, the flat [rows_p + 2, C]
    counts/f/g concat in 2-D (``n_local_rows`` = rows_p; defaults to C
    as a conservative floor when the shard shape is unknown).
    """
    c = cfg.n_clusters
    if cfg.col_axis is None:
        psum_bytes = 4 * (c + 2)
    else:
        rows = c if n_local_rows is None else n_local_rows
        psum_bytes = 4 * c * (rows + 2)
    return {"allgather": 1, "psum": 1, "psum_bytes": psum_bytes}


def _inner_shard_fn(x_local, lm_cols, lm_rows, diag_local, l_idx_cols,
                    l_idx_rows, u0_local, wgt_local, *,
                    cfg: DistributedInnerConfig,
                    row_strides: tuple[int, ...], d_size: int, m_size: int):
    body, cond, init, unpack = _body_factory(
        cfg, x_local, lm_cols, lm_rows, diag_local, l_idx_cols, l_idx_rows,
        wgt_local, x_local.shape[0], row_strides, d_size, m_size)
    state = jax.lax.while_loop(cond, body, init(u0_local))
    # NO fixpoint epilogue: the body syncs the stats of the labels it just
    # wrote, so at exit the carry already holds the final labels' stats
    # (and the cost of the assignment that produced them).
    u, totals, t, cost = unpack(state)
    f, g, counts = finalize_stats(*totals)
    return u, f, g, counts, t, cost


def distributed_kkmeans_fit(mesh: Mesh, x: Array, landmarks: Array,
                            l_idx: Array, diag_k: Array, u0: Array, *,
                            cfg: DistributedInnerConfig,
                            wgt: Array | None = None) -> DistInnerResult:
    """Run the distributed inner loop on ``mesh``.

    x:        [n, d]  mini-batch rows (sharded over row axes or replicated —
                      in_specs below enforce the row sharding).
    landmarks:[L, d]  landmark features (replicated input; the shard_map
                      slices it over the column axis internally; the row
                      side K_ll is row-sharded in 1-D, replicated in 2-D —
                      see module docstring).
    l_idx:    [L]     landmark indices into the mini-batch (replicated).
    diag_k:   [n]     K(x_i, x_i).
    u0:       [n]     initial labels.
    wgt:      [n]     optional row weights — 0 on the modulo-replicated
                      ghost rows that pad a non-divisible batch, so they
                      never count in the cost (default: all ones).
    """
    if wgt is None:
        wgt = jnp.ones((x.shape[0],), jnp.float32)
    return DistInnerResult(*_mesh_program(x, landmarks, l_idx, diag_k, u0,
                                          wgt, mesh=mesh, cfg=cfg))


# One compiled program per (mesh, cfg, shapes): run eagerly, the shard_map
# would execute op by op — every Gram-block intermediate then lands in HBM
# unfused (a resident 35,000^2 f32 block needs 4x its size) and a fit
# compiles hundreds of one-op programs.
@partial(jax.jit, static_argnames=("mesh", "cfg"))
def _mesh_program(x, landmarks, l_idx, diag_k, u0, wgt, *, mesh: Mesh,
                  cfg: DistributedInnerConfig):
    row_axes, col_axis = cfg.row_axes, cfg.col_axis
    d_size = 1
    for a in row_axes:
        d_size *= mesh.shape[a]
    m_size = mesh.shape[col_axis] if col_axis is not None else 1
    bad_n = x.shape[0] % d_size != 0
    bad_l = landmarks.shape[0] % d_size != 0 or landmarks.shape[0] % m_size != 0
    if bad_n or bad_l:
        raise ValueError(
            f"n={x.shape[0]} must divide row-axes size {d_size} and "
            f"|L|={landmarks.shape[0]} must divide both {d_size} and {m_size};"
            " round |L| up with num_landmarks(multiple_of=lcm(D, M))")

    # static row-major strides of the row axes (shard i of axis a starts
    # at axis_index(a) * stride(a) row blocks into the gathered vector —
    # the same order jax.lax.all_gather(..., tiled=True) concatenates).
    strides = []
    acc = 1
    for a in reversed(row_axes):
        strides.append(acc)
        acc *= mesh.shape[a]
    row_strides = tuple(reversed(strides))

    rowspec = P(row_axes)
    colspec = P(col_axis) if col_axis is not None else P()
    fn = partial(_inner_shard_fn, cfg=cfg, row_strides=row_strides,
                 d_size=d_size, m_size=m_size)
    shard_fn = shard_map(
        fn, mesh=mesh,
        in_specs=(
            P(row_axes, None),    # x rows
            P(col_axis, None) if col_axis else P(None, None),  # lm cols
            # lm rows: the 1-D K_ll block is row-sharded (the paper's
            # layout); 2-D replicates it over the row axes so g is local
            # after the gather and can join the fused stats psum.
            P(row_axes, None) if col_axis is None else P(None, None),
            P(row_axes),          # diag
            colspec,              # l_idx cols
            rowspec if col_axis is None else P(),  # l_idx rows
            rowspec,              # u0
            rowspec,              # wgt
        ),
        out_specs=(rowspec, P(row_axes, None), P(), P(), P(), P()),
        check_vma=False,
    )
    return shard_fn(x, landmarks, landmarks, diag_k, l_idx, l_idx, u0, wgt)
