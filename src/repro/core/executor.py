"""ScheduleExecutor — the converged AWB configuration as a first-class,
device-resident artifact (DESIGN.md §3).

AWB-GCN's engine "converges, then reuses the ideal configuration" (§IV):
the balancing effort is paid once per graph, and every subsequent round and
layer replays the converged plan. This module is purely the **execution
machinery** for that plan:

* One executor over **step shards** (``_ExecutorBase``): a schedule's
  gather slot stream is cut into contiguous step ranges, one per device —
  ``ScheduleExecutor`` holds one shard of every step on one device,
  ``ShardedScheduleExecutor`` one shard per device of a 1-D mesh, run
  under ``shard_map`` with a psum merge (DESIGN.md §4). Both share one
  chunk grid, one upload, one repair and one value patch; only the
  assembly of the jit operand differs. ``make_executor`` builds either
  from a ``TunedConfig`` and a placement.
* An executor uploads its schedule's arrays once at construction and
  serves ``spmm(b) = A @ b`` (fused-gather VPU routing or step-scanned
  one-hot MXU routing, chosen by ``select_routing``'s cost model), a
  whole-GCN ``forward`` and its request-batched ``forward_batch``, and,
  on one device, the GAT's edge-attention forward (``gat_forward_batch``,
  ``core.gat``) over the same gather slot stream. The compiled programs
  take the schedule arrays as arguments, keyed on a static ``Geometry``,
  so they never hold a copy of the schedule and executors of equal
  geometry share them.
* A streaming update builds a new executor from the old one
  (``repaired_executor``, ``value_patched_executor``, DESIGN.md §11): per
  shard, the device arrays are kept, scatter-patched or re-uploaded, and
  the result equals a cold build bit for bit.

Every caching/search concern that used to live here — fingerprint-keyed
schedule/executor caches, the measured autotune sweep, ``TunedConfig`` —
moved to the ``repro.tuning`` package (``registry``, ``runner``, ``space``,
``store``); this module lazily re-exports those names so existing call
sites (``executor.get_executor``, ``executor.autotune``, …) keep working.

Routing paths
-------------
``gather``  — per-slot ``jnp.take`` of B rows + one fused scatter-add
              straight into output rows (``row_map∘slot`` precomposed at
              upload time). Routing work scales with the slot count alone;
              the right choice for ultra-sparse operands and the only
              sensible choice off-TPU.
``onehot``  — a ``lax.scan`` over steps replaying the Pallas kernel's MXU
              contractions (one-hot gather [K, CB] @ B-block, one-hot
              scatter [K, R]ᵀ @ contributions). Routing work scales with
              K·CB per step — viable only with a capped ``cols_per_block``;
              kept exactly kernel-shaped so it doubles as the measurable
              stand-in for the dense-routing Pallas path in benchmarks and
              equivalence tests.

Both executors accept ``bf16_accumulate=True`` to run the routing bodies'
multiplies and accumulations in bfloat16 (a sweep axis — the autotuner
attaches an f32-vs-bf16 max-error report to the winning ``TunedConfig``).
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.gat import NEGATIVE_SLOPE
from repro.core.schedule import Schedule
from repro.lazyexports import lazy_exports
from repro.sharding.schedule_shard import shard_schedule, split_step_ranges

GATHER = "gather"
ONEHOT = "onehot"

#: floor (total slot-array bytes) below which a repair re-uploads in full
#: instead of scatter-patching dirty slots on device. The scoped scatter
#: saves transfer bandwidth on accelerator-scale graphs but costs an XLA
#: scatter dispatch (and an occasional compile) that a small graph's plain
#: re-upload beats; tests pin this to 0 to exercise the scoped path.
SCOPED_UPLOAD_MIN_BYTES = 16 * 1024 * 1024


@jax.jit
def _scatter_set(dev: jax.Array, idx: jax.Array, v: jax.Array) -> jax.Array:
    """Copy-on-write point update of a chunked device array: one jitted
    (hence shape-cached) scatter instead of eager per-op dispatch — the
    value-patch fast lane calls this on every streaming update, so its
    dispatch overhead is on the repair-latency critical path."""
    return dev.reshape(-1).at[idx].set(v).reshape(dev.shape)


# cost-model constants (v5e-class core): 128×128 MXU MAC/cycle, and a
# dynamic-gather bandwidth proxy for VMEM row fetches on the VPU path
_MXU_MACS_PER_CYCLE = 16384
_GATHER_BYTES_PER_CYCLE = 512


def routing_cost_model(k: int, cb: int, r: int, ktile: int = 128) -> dict:
    """Estimated per-step cycles of each routing path (relative units).

    one-hot: two MXU contractions, [K, CB] @ [CB, ktile] and
    [K, R]ᵀ @ [K, ktile] → K·(CB+R)·ktile MACs.
    gather: K dynamic row fetches of a ktile-wide f32 row (latency/bandwidth
    bound on the VPU) + the same one-hot scatter contraction.
    """
    onehot = k * (cb + r) * ktile / _MXU_MACS_PER_CYCLE
    gather = (
        k * ktile * 4 / _GATHER_BYTES_PER_CYCLE + k * r * ktile / _MXU_MACS_PER_CYCLE
    )
    return {ONEHOT: onehot, GATHER: gather}


def select_routing(k: int, cb: int, r: int, ktile: int = 128) -> str:
    """Pick the cheaper routing for one operand: one-hot MXU routing wins
    when the column block is capped small; gather wins when the block spans
    a wide (ultra-sparse) operand."""
    cost = routing_cost_model(k, cb, r, ktile)
    return ONEHOT if cost[ONEHOT] <= cost[GATHER] else GATHER


class InjectedFault(RuntimeError):
    """Raised by ``FaultInjector.check`` at an armed seam (the default
    exception type; ``arm(exc=...)`` substitutes another)."""


#: wildcard filter value for FaultInjector.arm — matches any context
ANY = object()


class FaultInjector:
    """Deterministic failure injection for the executor/serving stack.

    Production code calls ``check(site, **ctx)`` at a few named seams;
    the call is free when nothing is armed, and raises when an armed
    fault matches. The seams:

    * ``"upload"``   — host→device array upload (``_placed``), context
      ``device=``: fails a device upload, e.g. mid re-admission.
    * ``"dispatch"`` — the serving engine's batch dispatch, context
      ``graph=``: fails the whole dispatch before any work is charged.
    * ``"replica_chunk"`` — one replica's sub-batch execution, context
      ``graph=``/``device=``: fails exactly one clone's chunk, leaving
      its siblings healthy.

    ``arm(site, times=n)`` fires the next ``n`` matching checks (filters
    ``graph=``/``device=`` restrict the match; default matches any).
    ``clear()`` disarms everything; ``fired`` logs each raised fault as
    ``(site, graph, device)`` for assertions. Test seam only — never arm
    in production code.
    """

    def __init__(self):
        self._armed: list = []
        self.fired: list = []

    def arm(
        self, site: str, *, times: int = 1, exc=None, graph=ANY, device=ANY
    ) -> None:
        self._armed.append(
            {
                "site": site,
                "times": int(times),
                "exc": exc,
                "graph": graph,
                "device": device,
            }
        )

    def clear(self) -> None:
        self._armed.clear()
        self.fired.clear()

    def check(self, site: str, *, graph=None, device=None) -> None:
        if not self._armed:
            return
        for f in self._armed:
            if f["site"] != site:
                continue
            if f["graph"] is not ANY and f["graph"] != graph:
                continue
            if f["device"] is not ANY and f["device"] != device:
                continue
            f["times"] -= 1
            if f["times"] <= 0:
                self._armed.remove(f)
            self.fired.append((site, graph, device))
            raise (
                f["exc"]
                if f["exc"] is not None
                else InjectedFault(
                    f"injected {site} fault (graph={graph!r}, "
                    f"device={device!r})"
                )
            )


#: process-wide injector instance the seams consult (tests arm/clear it)
FAULTS = FaultInjector()


# step-major device copies of schedule arrays, shared between
# ScheduleExecutor and the Pallas kernel wrapper so one schedule is
# uploaded once no matter who consumes it. Keyed on (schedule identity,
# placement device), bounded LRU — the serving tier places executors on
# specific mesh devices, and each placement owns its own copy.
_DEVICE_STEPS: "OrderedDict[tuple, tuple]" = OrderedDict()
_DEVICE_STEPS_CAP = 32


def _placed(x, device):
    """Upload ``x`` to ``device`` (None = jax's default placement)."""
    FAULTS.check("upload", device=device)
    if device is None:
        return jnp.asarray(x)
    return jax.device_put(jnp.asarray(x), device)


def device_step_arrays(sched: Schedule, device=None) -> dict:
    """Step-major jnp arrays of one schedule — ``val``/``lrow``/``lcol``
    reshaped [n_steps, K], ``win``/``cblk`` per step, ``row_map`` — uploaded
    once per (schedule instance, device) and memoized (bounded LRU)."""
    key = (id(sched), device)
    hit = _DEVICE_STEPS.get(key)
    if hit is not None and hit[0] is sched:
        _DEVICE_STEPS.move_to_end(key)
        return hit[1]
    n_steps, k = sched.n_steps, sched.nnz_per_step
    arrs = {
        "val": _placed(sched.val.reshape(n_steps, k), device),
        "lrow": _placed(sched.local_row.reshape(n_steps, k), device),
        "lcol": _placed(sched.local_col.reshape(n_steps, k), device),
        "win": _placed(sched.win_id, device),
        "cblk": _placed(sched.col_block, device),
        "row_map": _placed(sched.row_map, device),
    }
    _DEVICE_STEPS[key] = (sched, arrs)
    if len(_DEVICE_STEPS) > _DEVICE_STEPS_CAP:
        _DEVICE_STEPS.popitem(last=False)
    return arrs


#: sentinel for ``release_device_steps``: drop the copies on *every*
#: device (``None`` is a real placement handle — jax's default device —
#: so it cannot double as the catch-all)
ALL_DEVICES = object()


def release_device_steps(sched: Schedule, device=ALL_DEVICES) -> None:
    """Drop memoized device copies of one schedule's step arrays.

    The serving engine's eviction and ``tuning.registry.release_graph``
    call this so a one-hot executor's uploads don't outlive their owner —
    without it the identity-keyed LRU above keeps the arrays resident
    until 32 unrelated schedules displace them. Pass ``device`` (a
    placement handle, ``None`` meaning the default device) to drop only
    that device's copy — what dropping **one replica** of a multi-replica
    graph needs: the surviving replicas' uploads on other devices must
    stay resident."""
    sid = id(sched)
    if device is ALL_DEVICES:
        keys = [k for k in _DEVICE_STEPS if k[0] == sid]
    else:
        keys = [(sid, device)] if (sid, device) in _DEVICE_STEPS else []
    for key in keys:
        del _DEVICE_STEPS[key]


def _gather_slots(sched: Schedule):
    """Per-slot flat arrays of the fused-gather routing: global B-row
    ``gcol``, output row ``tgt`` (``row_map ∘ slot`` precomposed: the
    scatter epilogue folds into the main scatter — padding slots carry
    ``val == 0``, so a clamped target row accumulates nothing), and the
    slot values. All step-major, length ``n_steps * nnz_per_step``."""
    m, n = sched.shape
    k = sched.nnz_per_step
    r = sched.rows_per_window
    cb = sched.cols_per_block
    win_slot = np.repeat(sched.win_id.astype(np.int64), k)
    cblk_slot = np.repeat(sched.col_block.astype(np.int64), k)
    gcol = np.minimum(cblk_slot * cb + sched.local_col, n - 1)
    slot = win_slot * r + sched.local_row
    tgt = np.maximum(sched.row_map[slot], 0).astype(np.int32)
    return gcol.astype(np.int32), tgt, sched.val


def _gather_slots_steps(sched: Schedule, steps: np.ndarray):
    """``_gather_slots`` restricted to the given step indices — what the
    repair path computes for re-emitted steps only, instead of re-deriving
    the whole slot stream."""
    _, n = sched.shape
    k = sched.nnz_per_step
    r = sched.rows_per_window
    cb = sched.cols_per_block
    steps = np.asarray(steps, np.int64)
    sl = (steps[:, None] * k + np.arange(k, dtype=np.int64)).reshape(-1)
    win = np.repeat(sched.win_id[steps].astype(np.int64), k)
    cblk = np.repeat(sched.col_block[steps].astype(np.int64), k)
    gcol = np.minimum(cblk * cb + sched.local_col[sl], n - 1).astype(np.int32)
    tgt = np.maximum(sched.row_map[win * r + sched.local_row[sl]], 0).astype(np.int32)
    return gcol, tgt, sched.val[sl]


def _window_operands(sched: Schedule, n_chunks: int, chunk: int):
    """Host arrays of the attention's window-local reductions over a slot
    stream chunked ``[n_chunks, chunk]`` (``chunk`` a multiple of K), and
    whether every step is its own window in one chunk (then ``win`` is
    left out: the step→window map is the identity):

    * ``win`` ``[n_chunks, chunk // K]``: each step's window (0 for the
      last chunk's padding steps, whose slots are all ``val == 0``);
    * ``row_slot`` ``[m]``: each row's first window slot (``n_windows·r``,
      out of range, for a row no slot maps to);
    * ``xslot``/``xrow``: a split row's further window slots (its evil
      chunks after the first) and their owner rows — empty when no row is
      split."""
    m = sched.shape[0]
    k, n_steps = sched.nnz_per_step, sched.n_steps
    in_order = np.array_equal(sched.win_id, np.arange(sched.n_windows))
    one_step = n_chunks == 1 and in_order
    slots = np.nonzero(sched.row_map >= 0)[0]
    order = np.argsort(sched.row_map[slots], kind="stable")
    slots = slots[order].astype(np.int32)
    rows = sched.row_map[slots]
    first = np.ones(rows.shape, bool)
    first[1:] = rows[1:] != rows[:-1]
    row_slot = np.full(m, sched.n_windows * sched.rows_per_window, np.int32)
    row_slot[rows[first]] = slots[first]
    ops = {"row_slot": row_slot, "xslot": slots[~first], "xrow": rows[~first]}
    if not one_step:
        win = np.zeros(n_chunks * (chunk // k), np.int32)
        win[:n_steps] = sched.win_id
        ops["win"] = win.reshape(n_chunks, -1)
    return ops, one_step


def _spliced_host_slots(old_host, new_sched: Schedule, repair):
    """Host gather-slot arrays of a repaired schedule, spliced from the old
    executor's retained host slots plus freshly derived slots for the
    re-emitted steps. Returns ``(gcol, tgt, val, moved)`` where ``moved``
    flags steps whose *device position or content* changed — the scoped
    re-upload set. Reused steps carry their slot payloads verbatim: the
    repair guarantees window-aligned steps keep identical ``gcol`` (same
    local cols/blocks), ``tgt`` (the new row_map holds the same row values
    at the remapped window slots) and ``val``."""
    og, ot, ov = old_host
    k = new_sched.nnz_per_step
    src = np.asarray(repair.step_src, np.int64)
    s_new = src.shape[0]
    if s_new != new_sched.n_steps:
        raise ValueError("step_src does not match the repaired schedule")
    moved = src != np.arange(s_new, dtype=np.int64)
    reused = src >= 0
    fresh = np.nonzero(~reused)[0]
    if fresh.size:
        fg, ft, fv = _gather_slots_steps(new_sched, fresh)
    else:
        fg = ft = fv = None

    def take(oa, fa, dtype):
        out = np.empty((s_new, k), dtype)
        out[reused] = oa.reshape(-1, k)[src[reused]]
        if fa is not None:
            out[~reused] = fa.reshape(-1, k)
        return out.reshape(-1)

    gcol = take(og, fg, np.int32)
    tgt = take(ot, ft, np.int32)
    val = take(ov, fv, ov.dtype)
    return gcol, tgt, val, moved


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The static facts a routing body specializes on — everything but the
    schedule arrays themselves, which every jitted entry point takes as
    arguments. Two executors with equal geometry (a shape-preserving repair
    of one graph, or two graphs of one shape) share compiled programs, and
    the compiled program's size does not grow with nnz."""

    routing: str
    m: int
    #: one-hot path only (0 on the gather path): A's column count, window
    #: rows, column-block width and window count. A gather executor's
    #: ``attention_geometry`` sets ``r`` and ``n_windows`` too
    n: int
    r: int
    cb: int
    n_windows: int
    #: chunk count of the gather slot stream (0 on the one-hot path)
    n_chunks: int
    bf16: bool
    #: outputs come back through the ``unperm`` row permutation operand
    unperm: bool = False
    #: 1-D mesh of the sharded executor (None: one device)
    mesh: Optional[Mesh] = None
    #: ``attention_geometry`` only (0 and False elsewhere): slots per step,
    #: and whether the stream is one chunk of steps that are each their
    #: own window, in order — the window combine is then the identity
    k: int = 0
    one_step_windows: bool = False

    @property
    def acc(self):
        return jnp.bfloat16 if self.bf16 else jnp.float32


def _gather_partial(gcol, tgt, val, bf, *, g: Geometry):
    """Fused-gather routing over one slot stream ``[n_chunks, chunk]``: B-row
    gather per slot, one scatter-add into final output rows (row_map
    precomposed). Chunked so the [chunk, kdim] intermediate stays bounded
    on million-edge graphs."""
    acc = g.acc
    out = jnp.zeros((g.m, bf.shape[1]), acc)
    if g.n_chunks == 1:
        rows = jnp.take(bf, gcol[0], axis=0) * val[0].astype(acc)[:, None]
        return out.at[tgt[0]].add(rows)

    def chunk(i, a_):
        rows = jnp.take(bf, gcol[i], axis=0) * val[i].astype(acc)[:, None]
        return a_.at[tgt[i]].add(rows)

    return jax.lax.fori_loop(0, g.n_chunks, chunk, out)


def _onehot_partial(win, cblk, val, lrow, lcol, rm, bf, *, g: Geometry):
    """Dense-routing emulation over one step stream: scan over steps, each
    doing the Pallas kernel's two one-hot MXU contractions against the
    step's [CB, kdim] B-panel, then the scatter epilogue (adder tree:
    permuted window slots → matrix rows). The measurable XLA twin of the
    kernel."""
    acc = g.acc
    kdim = bf.shape[1]
    cb, r = g.cb, g.r
    ncb = -(-g.n // cb)
    bp = jnp.pad(bf, ((0, ncb * cb - g.n), (0, 0))).reshape(ncb, cb, kdim)

    # HIGHEST keeps the one-hot selections exact in f32 on a TPU, whose
    # default matmul precision rounds f32 operands to bfloat16
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    def step(out_perm, s):
        w, cblk_s, val_s, lrow_s, lcol_s = s
        bb = bp[cblk_s]  # [CB, kdim]
        gather = (lcol_s[:, None] == jnp.arange(cb)[None, :]).astype(acc)  # [K, CB]
        contrib = dot(gather, bb) * val_s.astype(acc)[:, None]  # [K, kdim]
        scatter = (lrow_s[:, None] == jnp.arange(r)[None, :]).astype(acc)  # [K, R]
        return out_perm.at[w].add(dot(scatter.T, contrib)), None

    out_perm = jnp.zeros((g.n_windows, r, kdim), acc)
    out_perm, _ = jax.lax.scan(step, out_perm, (win, cblk, val, lrow, lcol))
    valid = rm >= 0
    contrib = jnp.where(valid[:, None], out_perm.reshape(-1, kdim), 0.0)
    return jnp.zeros((g.m, kdim), acc).at[jnp.where(valid, rm, 0)].add(contrib)


def _spmm_body(geom: Geometry, ops: dict, b: jax.Array) -> jax.Array:
    """C = A @ b for the schedule arrays ``ops``. On a mesh each device runs
    the single-device body over its own step shard under ``shard_map`` and
    a ``psum`` merges the partial outputs — the distributed adder tree that
    also reunites evil-row chunks and windows straddling a shard
    boundary."""
    bf = b.astype(geom.acc)
    if geom.routing == GATHER:
        partial = functools.partial(_gather_partial, g=geom)
        stepwise = [ops["gcol"], ops["tgt"], ops["val"]]
        shared = []
    else:
        partial = functools.partial(_onehot_partial, g=geom)
        stepwise = [ops[key] for key in ("win", "cblk", "val", "lrow", "lcol")]
        shared = [ops["row_map"]]
    if geom.mesh is None:
        out = partial(*stepwise, *shared, bf)
    else:
        axis = geom.mesh.axis_names[0]
        nk = len(stepwise)

        def body(*arrs):
            local = [x[0] for x in arrs[:nk]]  # drop the device dim
            return jax.lax.psum(partial(*local, *arrs[nk:]), axis)

        # step-sharded schedule arrays; row_map and the operand replicated.
        # check_vma=False: the body ends in an explicit psum, which makes
        # the P() output replicated by construction
        specs = (P(axis),) * nk + (P(),) * (len(shared) + 1)
        out = jax.shard_map(
            body, mesh=geom.mesh, in_specs=specs, out_specs=P(), check_vma=False
        )(*stepwise, *shared, bf)
    if geom.unperm:
        with jax.named_scope("unperm"):
            out = jnp.take(out, ops["unperm"], axis=0)
    return out.astype(b.dtype)


def _forward_body(geom: Geometry, ops: dict, params: dict, x: jax.Array) -> jax.Array:
    """Whole-GCN logits: every layer runs A × (X × W) through the schedule.
    Each layer's ops carry the scopes ``l<i>.xw``, ``l<i>.spmm`` and
    ``l<i>.relu`` in their metadata, for the profiler."""
    h = x
    n_layers = len(params)
    for i in range(n_layers):
        with jax.named_scope(f"l{i}.xw"):
            h = h @ params[f"w{i}"]
        with jax.named_scope(f"l{i}.spmm"):
            h = _spmm_body(geom, ops, h)
        if i < n_layers - 1:
            with jax.named_scope(f"l{i}.relu"):
                h = jax.nn.relu(h)
    return h


def _batched_forward_body(
    geom: Geometry, ops: dict, params: dict, xs: jax.Array
) -> jax.Array:
    """``_forward_body`` vmapped over a leading request axis of ``xs``."""
    fwd = functools.partial(_forward_body, geom)
    return jax.vmap(fwd, in_axes=(None, None, 0))(ops, params, xs)


class UnsupportedRoutingError(ValueError):
    """An architecture's body has no implementation on this executor's
    routing or mesh: the GAT's attention body runs on the gather routing
    of one device."""


def _over_chunks(body, init, *, n_chunks: int):
    """``body(i, carry)`` over the slot stream's chunks; a single chunk is
    one straight-line call, as ``_gather_partial`` does."""
    if n_chunks == 1:
        return body(0, init)
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _window_reduce(step_part, ops, *, g: Geometry, width: int, identity, scatter):
    """Reduce per-step window partials onto matrix rows: ``step_part(i)``
    gives chunk ``i``'s steps as ``[steps, r, width]`` partials of their
    windows' local rows. A window's steps (contiguous, possibly across two
    chunks) are combined by ``scatter`` over step indices, skipped where
    every step is its own window (``g.one_step_windows``); then each row
    gathers its first window slot through ``row_slot`` and ``scatter``
    folds in a split row's further chunk slots (``xslot`` → ``xrow``).
    ``identity`` is what a slot no step wrote reads."""
    if g.one_step_windows:
        flat = step_part(0).reshape(-1, width)
    else:

        def combine(i, windows):
            return scatter(windows, ops["win"][i], step_part(i))

        init = jnp.full((g.n_windows, g.r, width), identity, g.acc)
        flat = _over_chunks(combine, init, n_chunks=g.n_chunks).reshape(-1, width)
    rows = jnp.take(flat, ops["row_slot"], axis=0, mode="fill", fill_value=identity)
    if ops["xslot"].shape[0]:
        rows = scatter(rows, ops["xrow"], flat[ops["xslot"]])
    return rows


def _attention_partial(ops, s_row, s_col, wh, *, g: Geometry, layer: int):
    """Edge attention over one slot stream ``[n_chunks, chunk]``, the GAT's
    A-side of a layer: per slot and head the score ``LeakyReLU(s_row[tgt] +
    s_col[gcol])``, a softmax over each output row, and the sum of ``Wh``
    rows weighted by it. ``s_row`` ``[m, H]`` (H heads) is in the stream's
    row order, ``s_col`` ``[n, H]`` and ``wh`` ``[n, H·F]`` in node order;
    returns ``[m, H, F]`` in the stream's row order.

    Both row reductions are window-local (``_window_reduce``): a step's
    slots all write one window of ``g.r`` rows, so each step reduces its
    slots densely onto those rows — the row max as a masked max over the
    step's slots, the exp-weighted ``[Wh ‖ 1]`` sum (whose last column per
    head is the softmax's denominator) as a one-hot ``[r, k]`` contraction
    on the MXU, ``k = g.k`` slots per step — and only window slots are
    mapped to rows. The row max is
    complete, split rows included, before any ``exp``. Slots with ``val ==
    0`` — chunk padding, and empty PE slots — are masked out of both."""
    gcol, tgt, val, lrow = ops["gcol"], ops["tgt"], ops["val"], ops["lrow"]
    acc = g.acc
    h = s_row.shape[1]
    f = wh.shape[1] // h
    steps = gcol.shape[1] // g.k
    # HIGHEST keeps the one-hot selection exact and the sums in float32 on
    # a TPU, as in ``_onehot_partial``
    dot = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def scores(i):
        with jax.named_scope(f"l{layer}.score"):
            e = s_row[tgt[i]] + s_col[gcol[i]]  # [chunk, heads]
            e = jnp.where(e > 0, e, NEGATIVE_SLOPE * e).reshape(steps, g.k, h)
            return e, (val[i] != 0).reshape(steps, g.k, 1)

    def onehot(i):  # [steps, k, r]: each slot's row in its step's window
        return lrow[i].reshape(steps, g.k)[:, :, None] == jnp.arange(g.r)

    def step_max(i):
        e, live = scores(i)
        with jax.named_scope(f"l{layer}.softmax"):
            e = jnp.where(live, e, -jnp.inf)
            # [steps, k, heads, r], reduced over k: the r rows lie on the
            # lanes (heads there would pad them 16-fold), and the masked
            # operand is fused into the reduction, never materialised
            top = jnp.where(onehot(i)[:, :, None], e[..., None], -jnp.inf).max(1)
            return top.transpose(0, 2, 1)

    def scatter_max(a, idx, v):
        return a.at[idx].max(v)

    def scatter_add(a, idx, v):
        return a.at[idx].add(v)

    top = _window_reduce(
        step_max, ops, g=g, width=h, identity=-jnp.inf, scatter=scatter_max
    )

    def step_sum(i):
        e, live = scores(i)
        with jax.named_scope(f"l{layer}.softmax"):
            shift = top[tgt[i]].reshape(steps, g.k, h)
            p = jnp.where(live, jnp.exp(e - shift), 0.0)  # [steps, k, heads]
        with jax.named_scope(f"l{layer}.aggregate"):
            wh_i = jnp.take(wh, gcol[i], axis=0).reshape(steps, g.k, h, f)
            rows = jnp.concatenate([p[..., None] * wh_i, p[..., None]], axis=3)
            rows = rows.reshape(steps, g.k, h * (f + 1))
            return dot("skr,skc->src", onehot(i).astype(acc), rows)

    with jax.named_scope(f"l{layer}.aggregate"):
        sums = _window_reduce(
            step_sum, ops, g=g, width=h * (f + 1), identity=0.0, scatter=scatter_add
        )
        sums = sums.reshape(g.m, h, f + 1)
        den = sums[:, :, f:]
        return sums[:, :, :f] / jnp.where(den > 0, den, 1.0)


def _gat_forward_body(
    ops: dict, params: dict, x: jax.Array, *, geom: Geometry
) -> jax.Array:
    """Whole-GAT logits (``core.gat``): per layer ``l<i>.xw`` X·W, then
    ``l<i>.score``, ``l<i>.softmax`` and ``l<i>.aggregate`` over the gather
    slot stream (``_attention_partial``), then ``l<i>.elu`` on the
    concatenated heads or ``l<i>.mean`` of the last layer's heads. Scores,
    softmax and sums run in ``geom.acc``."""
    acc = geom.acc
    n_layers = len(params) // 2
    h = x
    for i in range(n_layers):
        att = params[f"a{i}"]
        k, f = att.shape[0], att.shape[1] // 2
        with jax.named_scope(f"l{i}.xw"):
            wh = (h @ params[f"w{i}"]).astype(acc)
        with jax.named_scope(f"l{i}.score"):
            whk = wh.reshape(-1, k, f)
            s_row = (whk * att[:, :f].astype(acc)).sum(-1)
            s_col = (whk * att[:, f:].astype(acc)).sum(-1)
            if geom.unperm:
                # a reordered stream's targets are permuted rows: row
                # ``unperm[v]`` holds node v, so its own score moves there
                s_row = jnp.zeros_like(s_row).at[ops["unperm"]].set(s_row)
        out = _attention_partial(ops, s_row, s_col, wh, g=geom, layer=i)
        if geom.unperm:
            with jax.named_scope("unperm"):
                out = jnp.take(out, ops["unperm"], axis=0)
        out = out.astype(x.dtype)
        if i < n_layers - 1:
            with jax.named_scope(f"l{i}.elu"):
                h = jax.nn.elu(out.reshape(out.shape[0], k * f))
        else:
            with jax.named_scope(f"l{i}.mean"):
                h = out.mean(axis=1)
    return h


def _batched_gat_body(
    geom: Geometry, ops: dict, params: dict, xs: jax.Array
) -> jax.Array:
    """``_gat_forward_body`` vmapped over a leading request axis of ``xs``."""
    fwd = functools.partial(_gat_forward_body, geom=geom)
    return jax.vmap(fwd, in_axes=(None, None, 0))(ops, params, xs)


# module-level jits keyed on the static geometry: executors of one geometry
# share their compiled programs
_spmm_jit = jax.jit(_spmm_body, static_argnames="geom")
_forward_jit = jax.jit(_forward_body, static_argnames="geom")
_batched_forward_jit = jax.jit(_batched_forward_body, static_argnames="geom")
_batched_gat_jit = jax.jit(_batched_gat_body, static_argnames="geom")


def _bucketed(idx: np.ndarray, floor: int) -> np.ndarray:
    """Scatter indices padded to a size in ``floor · 4^j`` by repeating the
    last one (a duplicate ``.set`` of an identical value is harmless), so
    repeated small patches reuse a handful of compiled ``_scatter_set``
    programs instead of compiling one per dirty-set size."""
    size = floor
    while size < idx.size:
        size *= 4
    pad = np.full(size - idx.size, idx[-1], idx.dtype)
    return np.concatenate([idx, pad]).astype(np.int32)


def _sharded_step_arrays(sched: Schedule, mesh: Mesh) -> dict:
    """The one-hot routing's step-major arrays on a mesh: ``shard_schedule``
    stacks, each device's shard placed with ``P(axis)``; ``row_map``
    replicated, since the epilogue runs device-local, before the psum."""
    shards = shard_schedule(sched, mesh.devices.size)

    def put(x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    axis = P(mesh.axis_names[0])
    keys = ("val", "lrow", "lcol", "win", "cblk")
    arrs = {key: put(getattr(shards, key), axis) for key in keys}
    arrs["row_map"] = put(sched.row_map, P())
    return arrs


#: what a repaired or value-patched executor carries over from its
#: predecessor unchanged: the execution fields and the placement
_CARRIED = (
    "ktile",
    "routing",
    "bf16_accumulate",
    "device",
    "mesh",
    "_slot_chunk_arg",
    "row_unperm",
    "_unperm",
)


class _ExecutorBase:
    """Device-resident executor of one converged AWB schedule, over step
    shards.

    The gather slot stream is held as contiguous step ranges
    (``split_step_ranges``), one per device: one device is one shard of
    every step, a 1-D mesh one shard per mesh device. Each shard is cut
    into the same chunk grid — whole steps per chunk, ``[n_chunks,
    chunk]``, the last chunk padded with ``val == 0`` slots — and uploaded
    to its device once. Only the assembly of the jit operand differs: one
    device takes its shard's array itself, a mesh the ``[D, n_chunks,
    chunk]`` array of its shards sharded ``P(axis)``. The one-hot routing
    keeps its step-major arrays (``device_step_arrays`` on one device,
    ``shard_schedule`` stacks on a mesh).

    The jitted entry points take the static ``geometry`` and the
    device-resident ``operands`` as arguments, so repeated ``spmm``/
    ``forward`` calls move only the dense operand. ``device_bytes`` is the
    resident footprint, what the serving engine's byte budget meters.

    ``device`` is the placement handle of a one-device executor: a specific
    ``jax.Device`` pins the schedule arrays (and so the computation, since
    ``commit`` moves operands there) to one device of a mesh; ``None``
    keeps jax's default placement, and is the value on a mesh.

    ``row_unperm`` supports locality-reordered schedules (core.reorder):
    when ``sched`` was built on a row-permuted graph, pass the inverse
    permutation (``inv[old_row] = new_row``) and every output comes back
    in **original** row order — one fused gather per call, bit-identical
    to executing the unpermuted schedule.
    """

    sched: Schedule
    routing: str
    #: the 1-D mesh of a sharded executor (None: one device)
    mesh: Optional[Mesh] = None

    def __init__(
        self,
        sched: Schedule,
        *,
        ktile: int = 128,
        routing: Optional[str] = None,
        bf16_accumulate: bool = False,
        slot_chunk: int = 1 << 18,
        device=None,
        row_unperm=None,
    ):
        self.sched = sched
        self.ktile = ktile
        self.bf16_accumulate = bf16_accumulate
        self.device = device
        self._slot_chunk_arg = slot_chunk
        k = sched.nnz_per_step
        r = sched.rows_per_window
        cb = sched.cols_per_block
        self.routing = routing or select_routing(k, cb, r, ktile)
        self.row_unperm = (
            None if row_unperm is None else np.asarray(row_unperm, np.int32)
        )
        if self.row_unperm is None:
            self._unperm = None
        elif self.mesh is None:
            self._unperm = _placed(self.row_unperm, device)
        else:  # replicated: the un-permute runs on the psum-merged output
            self._unperm = jax.device_put(
                jnp.asarray(self.row_unperm), NamedSharding(self.mesh, P())
            )
        self._build()

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    @property
    def _devices(self) -> list:
        """The shards' devices, in step order."""
        if self.mesh is None:
            return [self.device]
        return list(self.mesh.devices.reshape(-1))

    def _parts(self, arr: jax.Array) -> list:
        """Per-shard device arrays of one assembled slot stream."""
        if self.mesh is None:
            return [arr]
        by_dev = {s.device: s.data for s in arr.addressable_shards}
        return [by_dev[dev] for dev in self._devices]

    def _assembled(self, parts: list) -> jax.Array:
        """The jit operand of one slot stream from its shards' arrays."""
        if self.mesh is None:
            return parts[0]
        sharding = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        shape = (len(parts), *parts[0].shape[1:])
        return jax.make_array_from_single_device_arrays(shape, sharding, parts)

    def _build(self, old: "Optional[_ExecutorBase]" = None, moved=None) -> None:
        """Derive and upload the schedule's device arrays. Cold when ``old``
        is None; else the gather slot stream reuses ``old``'s shards where
        ``moved`` (per step: device position or content changed) allows,
        by the repair rule of ``_upload_shards``. Host copies of the slot
        stream are retained so a repair can splice new slot streams
        without re-deriving every step (DESIGN.md §11)."""
        self.step_ranges = split_step_ranges(self.sched.n_steps, self.n_devices)
        if self.routing != GATHER:
            # step-major arrays; on one device shared with the Pallas kernel
            # wrapper — one upload per (schedule, device), whoever asks
            if self.mesh is None:
                self._steps = device_step_arrays(self.sched, self.device)
            else:
                self._steps = _sharded_step_arrays(self.sched, self.mesh)
            self.scoped_upload = False
        else:
            if old is None:
                self._host = _gather_slots(self.sched)
            self._upload_shards(old, moved)
            if self.mesh is None:
                self._upload_windows(old)
        self._count_bytes()

    def _upload_shards(self, old, moved) -> None:
        """Chunk and upload each shard's slot streams (``gcol``, ``tgt``,
        ``val``, and on one device the attention's ``lrow``).

        The chunk grid holds as many whole steps per chunk as
        ``slot_chunk`` slots allow (at least one; the attention reduces
        whole steps onto their windows) over the longest shard, so the fused
        gather bounds its ``[chunk, kdim]`` intermediate. A repair (``old``
        given) treats each shard by one rule: a shard whose step range and
        grid are unchanged and that holds no moved step keeps its device
        arrays; one that moved at most half its slots, at or above
        ``SCOPED_UPLOAD_MIN_BYTES``, is scatter-patched copy-on-write (the
        old executor keeps serving in-flight batches untouched); any other
        is re-uploaded. Every result equals a cold build's bit for bit."""
        k = self.sched.nnz_per_step
        ranges = self.step_ranges.tolist()
        length = max(1, max(hi - lo for lo, hi in ranges)) * k
        chunk = max(k, min(self._slot_chunk_arg, length) // k * k)
        self._slot_chunk, self._n_chunks = chunk, -(-length // chunk)
        shape = (self._n_chunks, chunk)
        if self.mesh is not None:
            shape = (1, *shape)
        host = dict(zip(("gcol", "tgt", "val"), self._host))
        if self.mesh is None:
            host["lrow"] = self.sched.local_row
        grid = (self._n_chunks, chunk)
        same_grid = old is not None and (old._n_chunks, old._slot_chunk) == grid
        old_parts = {n: old._parts(old._streams[n]) for n in host} if same_grid else {}
        parts = {name: [] for name in host}
        kept = full = 0
        for d, ((lo, hi), dev) in enumerate(zip(ranges, self._devices)):
            n_slots = (hi - lo) * k
            steps = None
            if same_grid and [lo, hi] == old.step_ranges[d].tolist():
                steps = np.nonzero(moved[lo:hi])[0]
            if steps is not None and steps.size == 0:
                kept += 1
                for name in host:
                    parts[name].append(old_parts[name][d])
            elif (
                steps is not None
                and 2 * steps.size * k <= n_slots
                and n_slots * 12 >= SCOPED_UPLOAD_MIN_BYTES
            ):
                FAULTS.check("upload", device=dev)
                idx = (steps[:, None] * k + np.arange(k)).reshape(-1)
                idx = _bucketed(idx, 1024)
                for name, x in host.items():
                    old_part = old_parts[name][d]
                    parts[name].append(_scatter_set(old_part, idx, x[lo * k + idx]))
            else:
                full += 1
                for name, x in host.items():
                    padded = np.zeros(shape, x.dtype)
                    padded.reshape(-1)[:n_slots] = x[lo * k : hi * k]
                    parts[name].append(_placed(padded, dev))
        if kept == len(ranges):  # content and layout identical: share
            self._streams = {name: old._streams[name] for name in host}
        else:
            self._streams = {name: self._assembled(p) for name, p in parts.items()}
        #: True when a repair re-uploaded fewer than all shards in full
        self.scoped_upload = full < len(ranges)
        #: shards whose arrays a repair or value patch had to touch
        self.dirty_devices = len(ranges) - kept

    def _upload_windows(self, old: "Optional[_ExecutorBase]" = None) -> None:
        """Upload the attention's window arrays (``_window_operands``) —
        shared with ``old`` where its windows and chunk grid are this
        schedule's."""
        if (
            old is not None
            and old._n_chunks == self._n_chunks
            and old._slot_chunk == self._slot_chunk
            and np.array_equal(old.sched.win_id, self.sched.win_id)
            and np.array_equal(old.sched.row_map, self.sched.row_map)
        ):
            self._windows = old._windows
            self._one_step_windows = old._one_step_windows
        else:
            host, self._one_step_windows = _window_operands(
                self.sched, self._n_chunks, self._slot_chunk
            )
            self._windows = {key: _placed(v, self.device) for key, v in host.items()}

    def _count_bytes(self) -> None:
        arrays = list(self.operands.values())
        if self.routing == GATHER and self.mesh is None:
            arrays += [self._streams["lrow"], *self._windows.values()]
        self.device_bytes = int(sum(x.nbytes for x in arrays))

    def _successor(self, sched: Schedule) -> "_ExecutorBase":
        """An executor of ``sched`` with this one's execution fields and
        placement, for a repair or a value patch to fill in."""
        new = object.__new__(type(self))
        new.__dict__.update({key: getattr(self, key) for key in _CARRIED})
        new.sched = sched
        return new

    def _from_repair(self, new_sched: Schedule, repair) -> "_ExecutorBase":
        """Executor for a repaired schedule that reuses this executor's
        device buffers wherever the repair left steps untouched.

        The host slot stream is spliced (reused steps copy their old slot
        rows, re-emitted steps derive fresh ones), then each shard follows
        ``_upload_shards``' rule. One-hot routing and a fallback repair
        build cold."""
        new = self._successor(new_sched)
        if self.routing != GATHER or repair.fell_back or repair.step_src is None:
            new._build()
            return new
        *host, moved = _spliced_host_slots(self._host, new_sched, repair)
        new._host = tuple(host)
        new._build(self, moved)
        return new

    def _value_patched(
        self, new_sched: Schedule, slots: np.ndarray, vals: np.ndarray
    ) -> "_ExecutorBase":
        """Executor for a *value-only* patched schedule: the slot layout,
        shards and chunk grid are this executor's, only ``val`` changed at
        ``slots``. O(|delta|): the index streams and window arrays are
        shared outright, and only the dirty shards' ``val`` is
        scatter-patched (copy-on-write); an empty patch shares ``val``
        too. One-hot routing builds cold."""
        new = self._successor(new_sched)
        if self.routing != GATHER:
            new._build()
            return new
        gcol, tgt, oval = self._host
        val = oval.copy()
        val[slots] = np.asarray(vals, val.dtype)
        new._host = (gcol, tgt, val)
        new.step_ranges = self.step_ranges
        new._n_chunks, new._slot_chunk = self._n_chunks, self._slot_chunk
        if self.mesh is None:
            new._windows = self._windows
            new._one_step_windows = self._one_step_windows
        k = new_sched.nnz_per_step
        parts, dirty = [], 0
        ranges = self.step_ranges.tolist()
        old_parts = self._parts(self._streams["val"])
        for (lo, hi), dev, part in zip(ranges, self._devices, old_parts):
            local = slots[(slots >= lo * k) & (slots < hi * k)] - lo * k
            if local.size:
                FAULTS.check("upload", device=dev)
                idx = _bucketed(local, 64)
                part = _scatter_set(part, idx, val[lo * k + idx])
                dirty += 1
            parts.append(part)
        patched = self._assembled(parts) if dirty else self._streams["val"]
        new._streams = {**self._streams, "val": patched}
        new.scoped_upload = True
        new.dirty_devices = dirty
        new._count_bytes()
        return new

    # geometry and operands are computed once, on first use: an executor is
    # immutable after construction (repairs build a new one)
    @functools.cached_property
    def geometry(self) -> Geometry:
        s = self.sched
        m, n = s.shape
        if self.routing == GATHER:
            # the slot stream carries the window layout already: fields the
            # gather body never reads stay 0, so they cannot split programs
            n = r = cb = n_windows = 0
            n_chunks = self._n_chunks
        else:
            r, cb, n_windows = s.rows_per_window, s.cols_per_block, s.n_windows
            n_chunks = 0
        return Geometry(
            routing=self.routing,
            m=m,
            n=n,
            r=r,
            cb=cb,
            n_windows=n_windows,
            n_chunks=n_chunks,
            bf16=self.bf16_accumulate,
            unperm=self._unperm is not None,
            mesh=self.mesh,
        )

    @functools.cached_property
    def operands(self) -> dict:
        """The device-resident schedule arrays, as the jitted entry points
        take them."""
        if self.routing == GATHER:
            ops = {key: self._streams[key] for key in ("gcol", "tgt", "val")}
        else:
            ops = dict(self._steps)
        if self._unperm is not None:
            ops["unperm"] = self._unperm
        return ops

    @functools.cached_property
    def attention_geometry(self) -> Geometry:
        """``geometry`` with the window layout the GAT's window-local
        reductions specialize on (``_attention_partial``)."""
        s = self.sched
        return dataclasses.replace(
            self.geometry,
            k=s.nnz_per_step,
            r=s.rows_per_window,
            n_windows=s.n_windows,
            one_step_windows=self._one_step_windows,
        )

    @functools.cached_property
    def attention_operands(self) -> dict:
        """``operands`` plus the slots' local rows and the window arrays of
        ``_window_operands``: what ``gat_forward_batch`` takes."""
        return {**self.operands, "lrow": self._streams["lrow"], **self._windows}

    @property
    def attention_scatter_slots(self) -> int:
        """Window slots per layer whose partials still pass a scatter in
        the attention's reductions: a split row's chunks after its first
        (0 on the gather path of a schedule without split rows)."""
        return int(self._windows["xslot"].shape[0])

    def commit(self, x: jax.Array) -> jax.Array:
        """Commit a dense operand to this executor's placement device, so
        the computation runs where the schedule arrays already live."""
        if self.device is None:
            return x
        return jax.device_put(x, self.device)

    def spmm(self, b: jax.Array) -> jax.Array:
        """C = A @ b through the device-resident converged schedule."""
        if b.shape[0] != self.sched.shape[1]:
            raise ValueError(
                f"operand has {b.shape[0]} rows; schedule expects "
                f"{self.sched.shape[1]} (A is {self.sched.shape}) — XLA "
                "would silently clamp gather indices otherwise"
            )
        return _spmm_jit(self.geometry, self.operands, self.commit(b))

    __call__ = spmm

    def forward(self, params: dict, x: jax.Array) -> jax.Array:
        """Whole-GCN forward ``softmax-free`` logits: every layer runs
        A × (X × W) through this executor inside one jit."""
        if x.shape[0] != self.sched.shape[1]:
            raise ValueError(
                f"features have {x.shape[0]} rows; schedule expects "
                f"{self.sched.shape[1]} (A is {self.sched.shape})"
            )
        if self.device is not None:
            params = jax.tree.map(self.commit, params)
        return _forward_jit(self.geometry, self.operands, params, self.commit(x))

    def forward_batch(self, params: dict, xs: jax.Array) -> jax.Array:
        """``forward`` over a leading request axis — the serving engine's
        batch dispatch (one compiled program per geometry and batch size).
        ``params`` and ``xs`` must already live on this executor's
        placement."""
        return _batched_forward_jit(self.geometry, self.operands, params, xs)

    def _check_attention(self) -> None:
        if self.routing != GATHER or self.mesh is not None:
            where = (
                "a device mesh" if self.mesh is not None else f"{self.routing!r} routing"
            )
            raise UnsupportedRoutingError(
                f"the GAT's attention body runs on the gather routing of one "
                f"device, not on {where}"
            )

    def gat_forward_batch(self, params: dict, xs: jax.Array) -> jax.Array:
        """Whole-GAT logits (``core.gat`` parameters) over a leading request
        axis, through this executor's slot stream inside one jit — the
        serving engine's batch dispatch for a GAT graph. ``params`` and
        ``xs`` must already live on this executor's placement."""
        self._check_attention()
        return _batched_gat_jit(
            self.attention_geometry, self.attention_operands, params, xs
        )

    @property
    def utilization(self) -> float:
        return self.sched.utilization


class ScheduleExecutor(_ExecutorBase):
    """The executor of one schedule on one device: a single shard of every
    step (``_ExecutorBase``)."""


class ShardedScheduleExecutor(_ExecutorBase):
    """The executor of one schedule across a 1-D device mesh: one
    contiguous step shard per device (steps are equal work, so equal
    counts are balanced devices — the paper's equal-work distribution
    across the PE array, lifted one level to the device mesh).
    ``spmm``/``forward`` run the routing body per shard under
    ``shard_map`` and merge the per-device partial outputs with a
    ``psum`` — the distributed adder tree that also reunites evil-row
    chunks and boundary-straddling windows living on different devices.
    Numerics match the single-device executor up to f32 re-association of
    the cross-device sum.

    Pass ``mesh`` (1-D), or ``n_devices`` for the first ``n_devices`` of
    this host's devices (default: all)."""

    def __init__(
        self,
        sched: Schedule,
        *,
        n_devices: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        ktile: int = 128,
        routing: Optional[str] = None,
        bf16_accumulate: bool = False,
        slot_chunk: int = 1 << 18,
        row_unperm=None,
    ):
        if mesh is None:
            devs = jax.devices()
            if n_devices is None:
                n_devices = len(devs)
            if not 1 <= n_devices <= len(devs):
                raise ValueError(
                    f"n_devices={n_devices} but this host exposes "
                    f"{len(devs)} device(s)"
                )
            mesh = Mesh(np.asarray(devs[:n_devices]), ("dev",))
        else:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    "ShardedScheduleExecutor shards over one step axis and "
                    f"needs a 1-D mesh; got axes {mesh.axis_names}"
                )
            if n_devices is not None and n_devices != mesh.devices.size:
                raise ValueError(
                    f"n_devices={n_devices} contradicts the given mesh of "
                    f"{mesh.devices.size} device(s); pass one or the other"
                )
        self.mesh = mesh
        super().__init__(
            sched,
            ktile=ktile,
            routing=routing,
            bf16_accumulate=bf16_accumulate,
            slot_chunk=slot_chunk,
            row_unperm=row_unperm,
        )


class ExecutionConfig(NamedTuple):
    """The execution fields of a ``TunedConfig`` that ``make_executor``
    reads, for a caller that holds them as loose values."""

    ktile: int = 128
    routing: Optional[str] = None
    bf16_accumulate: bool = False


def make_executor(
    sched: Schedule,
    cfg,
    *,
    device=None,
    mesh: Optional[Mesh] = None,
    n_devices: Optional[int] = None,
    row_unperm=None,
) -> _ExecutorBase:
    """The executor of ``sched`` under ``cfg``'s execution fields
    (``ktile``, ``routing``, ``bf16_accumulate``: a ``TunedConfig`` or an
    ``ExecutionConfig``), placed on one device (``device``, a placement
    handle; None is jax's default device) or sharded by steps across a
    mesh (``mesh``, or ``n_devices`` of this host's devices)."""
    kwargs = dict(
        ktile=cfg.ktile,
        routing=cfg.routing,
        bf16_accumulate=cfg.bf16_accumulate,
        row_unperm=row_unperm,
    )
    if mesh is None and n_devices is None:
        return ScheduleExecutor(sched, device=device, **kwargs)
    return ShardedScheduleExecutor(sched, n_devices=n_devices, mesh=mesh, **kwargs)


def repaired_executor(old_ex, new_sched: Schedule, repair):
    """Executor for a repaired schedule, reusing ``old_ex``'s device
    buffers wherever the repair (``schedule.repair_schedule``) left steps
    untouched — the scoped re-upload path of DESIGN.md §11.

    Always returns a **new** executor object and never mutates ``old_ex``,
    so the serving tier can atomically swap while in-flight batches finish
    on the old one. Device state is bit-identical to a cold build on
    ``new_sched`` with the same construction arguments."""
    return old_ex._from_repair(new_sched, repair)


def value_patched_executor(old_ex, new_sched: Schedule, slots, vals):
    """Executor for a schedule produced by ``schedule.value_patch_schedule``
    — structure unchanged, only ``val[slots]`` differ from ``old_ex.sched``.

    The O(|delta|) fast lane of DESIGN.md §11: the index streams are
    shared with ``old_ex`` and only the dirty shards' ``val`` is
    scatter-patched. Same contract as ``repaired_executor``."""
    slots = np.asarray(slots, np.int64)
    return old_ex._value_patched(new_sched, slots, np.asarray(vals))


# ---------------------------------------------------------------------------
# Delegation: caching, fingerprints, and the autotune loop live in the
# repro.tuning package now. Resolved lazily (PEP 562) so importing this
# module never drags the tuning subsystem in — and so there is no import
# cycle (tuning.registry imports the executor classes above).
# ---------------------------------------------------------------------------

_TUNING_EXPORTS = {
    "graph_fingerprint": "repro.tuning.registry",
    "mesh_fingerprint": "repro.tuning.registry",
    "device_fingerprint": "repro.tuning.registry",
    "clear_caches": "repro.tuning.registry",
    "get_schedule": "repro.tuning.registry",
    "get_spmm_schedules": "repro.tuning.registry",
    "get_executor": "repro.tuning.registry",
    "executor_for_schedule": "repro.tuning.registry",
    "release_graph": "repro.tuning.registry",
    "TunedConfig": "repro.tuning.space",
    "default_sweep": "repro.tuning.space",
    "sharded_sweep": "repro.tuning.space",
    "sharded_device_counts": "repro.tuning.space",
    "density_matched_k": "repro.tuning.space",
    "autotune": "repro.tuning.runner",
    "autotuned_executor": "repro.tuning.runner",
    "warm_tuned_executor": "repro.tuning.runner",
    "time_call": "repro.tuning.runner",
}

__getattr__, __dir__ = lazy_exports(__name__, _TUNING_EXPORTS, globals())
