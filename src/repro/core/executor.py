"""ScheduleExecutor — the converged AWB configuration as a first-class,
device-resident artifact (DESIGN.md §3).

AWB-GCN's engine "converges, then reuses the ideal configuration" (§IV):
the balancing effort is paid once per graph, and every subsequent round and
layer replays the converged plan. This module is purely the **execution
machinery** for that plan:

* ``ScheduleExecutor`` uploads a ``Schedule``'s arrays to the device exactly
  once at construction and serves ``spmm(b) = A @ b`` (fused-gather VPU
  routing or step-scanned one-hot MXU routing, chosen by
  ``select_routing``'s cost model), a whole-GCN ``forward`` and its
  request-batched ``forward_batch``. The compiled programs take the schedule
  arrays as arguments, keyed on a static ``Geometry``, so they never hold a
  copy of the schedule and executors of equal geometry share them.
* ``ShardedScheduleExecutor`` runs the same plan across a 1-D device mesh
  (per-device step shards under ``shard_map``, psum merge — DESIGN.md §4).

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
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.schedule import Schedule
from repro.lazyexports import lazy_exports
from repro.sharding.schedule_shard import shard_schedule

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
    #: rows, column-block width and window count
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
        out = jnp.take(out, ops["unperm"], axis=0)
    return out.astype(b.dtype)


def _forward_body(geom: Geometry, ops: dict, params: dict, x: jax.Array) -> jax.Array:
    """Whole-GCN logits: every layer runs A × (X × W) through the schedule."""
    h = x
    n_layers = len(params)
    for i in range(n_layers):
        h = _spmm_body(geom, ops, h @ params[f"w{i}"])
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def _batched_forward_body(
    geom: Geometry, ops: dict, params: dict, xs: jax.Array
) -> jax.Array:
    """``_forward_body`` vmapped over a leading request axis of ``xs``."""
    fwd = functools.partial(_forward_body, geom)
    return jax.vmap(fwd, in_axes=(None, None, 0))(ops, params, xs)


# module-level jits keyed on the static geometry: executors of one geometry
# share their compiled programs
_spmm_jit = jax.jit(_spmm_body, static_argnames="geom")
_forward_jit = jax.jit(_forward_body, static_argnames="geom")
_batched_forward_jit = jax.jit(_batched_forward_body, static_argnames="geom")


class _ExecutorBase:
    """Shared surface of the single- and multi-device executors: operand
    validation and the call protocol of the jitted entry points, which take
    the executor's static ``geometry`` and its device-resident schedule
    arrays (``operands``) as arguments."""

    sched: Schedule
    routing: str
    bf16_accumulate: bool = False
    #: placement handle: the specific mesh device this executor's arrays
    #: live on (None = jax's default device; always None for the sharded
    #: executor, whose mesh is the placement).
    device = None
    #: the sharded executor's mesh (None: one device)
    mesh: Optional[Mesh] = None
    _unperm = None

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
            ops = {"gcol": self._gcol, "tgt": self._tgt, "val": self._val}
        else:
            ops = dict(self._steps)
        if self._unperm is not None:
            ops["unperm"] = self._unperm
        return ops

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

    @property
    def utilization(self) -> float:
        return self.sched.utilization


class ScheduleExecutor(_ExecutorBase):
    """Device-resident executor of one converged AWB schedule.

    Construction uploads every schedule array to one device once; the
    jitted entry points take those arrays as arguments, so repeated
    ``spmm``/``forward`` calls move only the dense operand. ``device_bytes``
    reports the resident footprint — what the serving engine's LRU budget
    meters.

    ``device`` is the placement handle: pass a specific ``jax.Device`` to
    pin the schedule arrays (and therefore the computation — operands are
    committed there by ``spmm``/``forward``) to one device of a mesh; the
    serving tier's ``MeshPlacer`` hands each graph such a handle. ``None``
    keeps jax's default placement.

    ``row_unperm`` supports locality-reordered schedules (core.reorder):
    when ``sched`` was built on a row-permuted graph, pass the inverse
    permutation (``inv[old_row] = new_row``) and every ``spmm``/``forward``
    output comes back in **original** row order — one fused gather per
    call, bit-identical to executing the unpermuted schedule.
    """

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
        self.row_unperm = (
            None if row_unperm is None else np.asarray(row_unperm, np.int32)
        )
        self._unperm = (
            None if self.row_unperm is None else _placed(self.row_unperm, device)
        )
        self._slot_chunk_arg = slot_chunk
        k = sched.nnz_per_step
        r = sched.rows_per_window
        cb = sched.cols_per_block
        self.routing = routing or select_routing(k, cb, r, ktile)
        #: set by the repair path: True when the last (re)construction
        #: uploaded only the dirty slot set instead of the full stream
        self.scoped_upload = False

        # ---- one-time host-side precompute + host→device upload ----------
        # only the selected routing's representation is built/uploaded
        if self.routing == GATHER:
            gcol, tgt, val = _gather_slots(sched)
            # host copies are retained so an incremental repair can splice
            # new slot streams without re-deriving every step (DESIGN.md §11)
            self._host = (gcol, tgt, val)

            # pad the flat slot stream to a whole number of chunks so the
            # fused gather path can bound its [chunk, kdim] intermediate
            s_total = gcol.shape[0]
            self._slot_chunk = int(min(slot_chunk, max(1, s_total)))
            pad = (-s_total) % self._slot_chunk
            self._n_chunks = (s_total + pad) // self._slot_chunk

            def _chunked(x, fill):
                return _placed(
                    np.concatenate([x, np.full(pad, fill, x.dtype)]).reshape(
                        self._n_chunks, self._slot_chunk
                    ),
                    device,
                )

            self._gcol = _chunked(gcol, 0)
            self._tgt = _chunked(tgt, 0)
            self._val = _chunked(val, 0.0)
            self.device_bytes = int(
                self._gcol.nbytes + self._tgt.nbytes + self._val.nbytes
            )
        else:
            # step-major arrays (shared with the Pallas kernel wrapper —
            # one upload per (schedule, device) no matter who consumes it)
            self._steps = device_step_arrays(sched, device)
            self.device_bytes = int(sum(v.nbytes for v in self._steps.values()))
        if self._unperm is not None:
            self.device_bytes += int(self._unperm.nbytes)

    @classmethod
    def _from_repair(
        cls, old_ex: "ScheduleExecutor", new_sched: Schedule, repair
    ) -> "ScheduleExecutor":
        """Executor for a repaired schedule that reuses the old executor's
        device buffers wherever the repair left steps untouched.

        GATHER: the host slot stream is spliced (reused steps copy their old
        slot rows, re-emitted steps derive fresh ones), and when the chunk
        grid is unchanged only the *moved* slots are scattered into the old
        device arrays (`.at[idx].set` — copy-on-write, so the old executor
        keeps serving in-flight batches untouched). ONEHOT or any fallback
        repair rebuilds from scratch — a fresh full upload.

        The result is a **new** executor object;
        never mutates ``old_ex``. Device contents are bit-identical to a
        cold ``ScheduleExecutor(new_sched, ...)`` with the same kwargs.
        """
        if (
            old_ex.routing != GATHER
            or repair.fell_back
            or repair.step_src is None
            or getattr(old_ex, "_host", None) is None
        ):
            return cls(
                new_sched,
                ktile=old_ex.ktile,
                routing=old_ex.routing,
                bf16_accumulate=old_ex.bf16_accumulate,
                slot_chunk=old_ex._slot_chunk_arg,
                device=old_ex.device,
                row_unperm=old_ex.row_unperm,
            )
        self = cls.__new__(cls)
        self.sched = new_sched
        self.ktile = old_ex.ktile
        self.bf16_accumulate = old_ex.bf16_accumulate
        self.device = old_ex.device
        self.routing = GATHER
        self._slot_chunk_arg = old_ex._slot_chunk_arg
        self.row_unperm = old_ex.row_unperm
        self._unperm = old_ex._unperm

        k = new_sched.nnz_per_step
        gcol, tgt, val, moved = _spliced_host_slots(old_ex._host, new_sched, repair)
        self._host = (gcol, tgt, val)
        s_total = gcol.shape[0]
        self._slot_chunk = int(min(self._slot_chunk_arg, max(1, s_total)))
        pad = (-s_total) % self._slot_chunk
        self._n_chunks = (s_total + pad) // self._slot_chunk
        # scoped patch is sound only on an identical padded grid — same
        # slot count (so the old padding region still pads) and same
        # chunking (so accumulation order, hence bitwise output, matches a
        # cold build)
        same_grid = (
            s_total == old_ex._host[0].shape[0]
            and self._slot_chunk == old_ex._slot_chunk
            and self._n_chunks == old_ex._n_chunks
        )
        n_moved = int(np.count_nonzero(moved)) * k
        if same_grid and n_moved == 0:
            # content and layout identical: the old device arrays ARE the
            # new ones (jax arrays are immutable — sharing is safe)
            self._gcol, self._tgt = old_ex._gcol, old_ex._tgt
            self._val = old_ex._val
            self.scoped_upload = True
        elif (
            same_grid
            and 2 * n_moved <= s_total
            and s_total * 12 >= SCOPED_UPLOAD_MIN_BYTES
        ):
            FAULTS.check("upload", device=self.device)
            steps = np.nonzero(moved)[0]
            idx = (steps[:, None] * k + np.arange(k, dtype=np.int64)).reshape(-1)
            # pad the scatter index to a coarse bucket (repeating the
            # last slot — duplicate .set with an identical value is
            # harmless) so repeated small updates reuse a handful of
            # compiled scatters instead of recompiling per dirty-set size
            bucket = 1024
            while bucket < idx.size:
                bucket *= 4
            if bucket > idx.size:
                idx = np.concatenate(
                    [idx, np.full(bucket - idx.size, idx[-1], idx.dtype)]
                )
            jidx = jnp.asarray(idx.astype(np.int32))

            def _patch(dev, host):
                flat = dev.reshape(-1).at[jidx].set(jnp.asarray(host[idx]))
                return flat.reshape(self._n_chunks, self._slot_chunk)

            self._gcol = _patch(old_ex._gcol, gcol)
            self._tgt = _patch(old_ex._tgt, tgt)
            self._val = _patch(old_ex._val, val)
            self.scoped_upload = True
        else:

            def _chunked(x, fill):
                return _placed(
                    np.concatenate([x, np.full(pad, fill, x.dtype)]).reshape(
                        self._n_chunks, self._slot_chunk
                    ),
                    self.device,
                )

            self._gcol = _chunked(gcol, 0)
            self._tgt = _chunked(tgt, 0)
            self._val = _chunked(val, 0.0)
            self.scoped_upload = False
        self.device_bytes = int(self._gcol.nbytes + self._tgt.nbytes + self._val.nbytes)
        if self._unperm is not None:
            self.device_bytes += int(self._unperm.nbytes)
        return self

    @classmethod
    def _value_patched(
        cls,
        old_ex: "ScheduleExecutor",
        new_sched: Schedule,
        slots: np.ndarray,
        vals: np.ndarray,
    ) -> "ScheduleExecutor":
        """Executor for a *value-only* patched schedule: structure (and
        therefore the slot layout, chunk grid, gcol/tgt streams) is
        byte-identical to ``old_ex``; only ``val`` changed, at ``slots``.

        O(|delta|): shares the old device ``_gcol``/``_tgt`` arrays
        outright and scatters just the changed values into ``_val``
        (copy-on-write — the old executor keeps serving untouched). The
        scatter index is padded to a small fixed bucket so every update of
        a given size class reuses one compiled scatter."""
        if old_ex.routing != GATHER or getattr(old_ex, "_host", None) is None:
            return cls(
                new_sched,
                ktile=old_ex.ktile,
                routing=old_ex.routing,
                bf16_accumulate=old_ex.bf16_accumulate,
                slot_chunk=old_ex._slot_chunk_arg,
                device=old_ex.device,
                row_unperm=old_ex.row_unperm,
            )
        self = cls.__new__(cls)
        self.sched = new_sched
        self.ktile = old_ex.ktile
        self.bf16_accumulate = old_ex.bf16_accumulate
        self.device = old_ex.device
        self.routing = GATHER
        self._slot_chunk_arg = old_ex._slot_chunk_arg
        self._slot_chunk = old_ex._slot_chunk
        self._n_chunks = old_ex._n_chunks
        self.row_unperm = old_ex.row_unperm
        self._unperm = old_ex._unperm

        gcol, tgt, oval = old_ex._host
        val = oval.copy()
        val[slots] = np.asarray(vals, val.dtype)
        self._host = (gcol, tgt, val)
        self._gcol, self._tgt = old_ex._gcol, old_ex._tgt
        if slots.size == 0:
            self._val = old_ex._val
        else:
            FAULTS.check("upload", device=self.device)
            idx = np.asarray(slots, np.int64)
            bucket = 64
            while bucket < idx.size:
                bucket *= 4
            if bucket > idx.size:
                idx = np.concatenate(
                    [idx, np.full(bucket - idx.size, idx[-1], idx.dtype)]
                )
            self._val = _scatter_set(old_ex._val, idx.astype(np.int32), val[idx])
        self.scoped_upload = True
        self.device_bytes = old_ex.device_bytes
        return self


class ShardedScheduleExecutor(_ExecutorBase):
    """Multi-device executor of one converged AWB schedule.

    The schedule is split by ``sharding.schedule_shard`` into contiguous
    per-device step shards (steps are equal work, so equal counts are
    balanced devices — the paper's equal-work distribution across the PE
    array, lifted one level to the device mesh). Construction uploads each
    shard to its own device exactly once (``device_put`` with a
    ``P('dev', ...)`` sharding on the stacked step axis); ``spmm``/
    ``forward`` then run the routing body under ``shard_map`` and merge the
    per-device partial outputs with a ``psum`` — the distributed adder
    tree that also reunites evil-row chunks and boundary-straddling
    windows living on different devices.

    Both routing paths shard identically: the step axis is the shard axis,
    and each device executes exactly the single-device body over its own
    steps. Numerics therefore match the single-device executor up to f32
    re-association of the cross-device sum.
    """

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
            n_devices = int(mesh.devices.size)
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_devices = n_devices
        self.sched = sched
        self.ktile = ktile
        self.bf16_accumulate = bf16_accumulate
        self._slot_chunk_arg = slot_chunk
        self.row_unperm = (
            None if row_unperm is None else np.asarray(row_unperm, np.int32)
        )
        # replicated — the un-permute runs on the psum-merged output
        self._unperm = (
            None
            if self.row_unperm is None
            else jax.device_put(
                jnp.asarray(self.row_unperm), NamedSharding(mesh, P())
            )
        )
        k = sched.nnz_per_step
        r = sched.rows_per_window
        cb = sched.cols_per_block
        self.routing = routing or select_routing(k, cb, r, ktile)
        #: set by the repair path: True when the last (re)construction
        #: re-uploaded only the device shards whose steps changed
        self.scoped_upload = False

        shards = shard_schedule(sched, n_devices)
        self.step_ranges = shards.ranges

        def put(x, *tail_spec):
            return jax.device_put(
                jnp.asarray(x), NamedSharding(mesh, P(self.axis, *tail_spec))
            )

        # ---- one-time host-side split + per-device upload ----------------
        if self.routing == GATHER:
            gcol, tgt, val = _gather_slots(sched)
            # retained for incremental repair splicing (DESIGN.md §11)
            self._host = (gcol, tgt, val)
            # per-device flat slot streams, padded to the common shard
            # length, then chunked so the [chunk, kdim] intermediate stays
            # bounded (same contract as the single-device executor)
            s_max = shards.steps_per_shard
            length = s_max * k
            self._slot_chunk = int(min(slot_chunk, max(1, length)))
            pad = (-length) % self._slot_chunk
            self._n_chunks = (length + pad) // self._slot_chunk

            def stack(x, fill):
                out = np.full((n_devices, length + pad), fill, x.dtype)
                for d, (lo, hi) in enumerate(shards.ranges):
                    out[d, : (hi - lo) * k] = x[lo * k : hi * k]
                return put(out.reshape(n_devices, self._n_chunks, self._slot_chunk))

            self._gcol = stack(gcol, 0)
            self._tgt = stack(tgt, 0)
            self._val = stack(val, 0.0)
            self.device_bytes = int(
                self._gcol.nbytes + self._tgt.nbytes + self._val.nbytes
            )
            if self._unperm is not None:
                self.device_bytes += int(self._unperm.nbytes)
        else:
            self._steps = {
                "val": put(shards.val),
                "lrow": put(shards.lrow),
                "lcol": put(shards.lcol),
                "win": put(shards.win),
                "cblk": put(shards.cblk),
                # replicated: the epilogue runs device-local, pre-psum
                "row_map": jax.device_put(
                    jnp.asarray(sched.row_map), NamedSharding(mesh, P())
                ),
            }
            self.device_bytes = int(sum(v.nbytes for v in self._steps.values()))
            if self._unperm is not None:
                self.device_bytes += int(self._unperm.nbytes)

    @classmethod
    def _from_repair(
        cls, old_ex: "ShardedScheduleExecutor", new_sched: Schedule, repair
    ) -> "ShardedScheduleExecutor":
        """Sharded executor for a repaired schedule, re-uploading only the
        device shards whose step range contains a moved/re-emitted step.

        The step count must be unchanged (the linspace split is then
        identical, so each clean device's stacked shard is byte-identical);
        otherwise — or for ONEHOT routing or a fallback repair — this
        rebuilds from scratch. Clean devices keep their existing on-device
        shard buffers via ``make_array_from_single_device_arrays``; the new
        executor is a distinct object, and the old
        one keeps serving in-flight batches."""
        if (
            old_ex.routing != GATHER
            or repair.fell_back
            or repair.step_src is None
            or getattr(old_ex, "_host", None) is None
            or new_sched.n_steps != old_ex.sched.n_steps
        ):
            return cls(
                new_sched,
                mesh=old_ex.mesh,
                ktile=old_ex.ktile,
                routing=old_ex.routing,
                bf16_accumulate=old_ex.bf16_accumulate,
                slot_chunk=old_ex._slot_chunk_arg,
                row_unperm=old_ex.row_unperm,
            )
        self = cls.__new__(cls)
        self.mesh = old_ex.mesh
        self.axis = old_ex.axis
        self.n_devices = old_ex.n_devices
        self.sched = new_sched
        self.ktile = old_ex.ktile
        self.bf16_accumulate = old_ex.bf16_accumulate
        self.routing = GATHER
        self._slot_chunk_arg = old_ex._slot_chunk_arg
        self.row_unperm = old_ex.row_unperm
        self._unperm = old_ex._unperm
        # n_steps unchanged ⇒ the deterministic linspace split is identical
        self.step_ranges = old_ex.step_ranges
        self._slot_chunk = old_ex._slot_chunk
        self._n_chunks = old_ex._n_chunks

        k = new_sched.nnz_per_step
        gcol, tgt, val, moved = _spliced_host_slots(old_ex._host, new_sched, repair)
        self._host = (gcol, tgt, val)
        n_devices = self.n_devices
        row_len = self._n_chunks * self._slot_chunk
        dirty = [bool(np.any(moved[lo:hi])) for lo, hi in self.step_ranges]
        devices = list(self.mesh.devices.reshape(-1))
        sharding = NamedSharding(self.mesh, P(self.axis))
        gshape = (n_devices, self._n_chunks, self._slot_chunk)

        def _restack(old_arr, flat, fill):
            by_dev = {s.device: s.data for s in old_arr.addressable_shards}
            parts = []
            for d, dev in enumerate(devices):
                lo, hi = self.step_ranges[d]
                if not dirty[d]:
                    parts.append(by_dev[dev])
                    continue
                FAULTS.check("upload", device=dev)
                row = np.full((1, row_len), fill, flat.dtype)
                row[0, : (hi - lo) * k] = flat[lo * k : hi * k]
                parts.append(
                    jax.device_put(
                        jnp.asarray(row.reshape(1, self._n_chunks, self._slot_chunk)),
                        dev,
                    )
                )
            return jax.make_array_from_single_device_arrays(gshape, sharding, parts)

        self._gcol = _restack(old_ex._gcol, gcol, 0)
        self._tgt = _restack(old_ex._tgt, tgt, 0)
        self._val = _restack(old_ex._val, val, 0.0)
        self.scoped_upload = not all(dirty)
        self.dirty_devices = int(sum(dirty))
        self.device_bytes = int(self._gcol.nbytes + self._tgt.nbytes + self._val.nbytes)
        if self._unperm is not None:
            self.device_bytes += int(self._unperm.nbytes)
        return self

    @classmethod
    def _value_patched(
        cls,
        old_ex: "ShardedScheduleExecutor",
        new_sched: Schedule,
        slots: np.ndarray,
        vals: np.ndarray,
    ) -> "ShardedScheduleExecutor":
        """Sharded executor for a value-only patched schedule: slot layout
        and step split are identical to ``old_ex``, only ``val`` changed at
        ``slots``. Shares the global ``_gcol``/``_tgt`` arrays and re-uploads
        just the ``_val`` shards of devices whose step range contains a
        changed slot; clean devices keep their existing shard buffers."""
        if old_ex.routing != GATHER or getattr(old_ex, "_host", None) is None:
            return cls(
                new_sched,
                mesh=old_ex.mesh,
                ktile=old_ex.ktile,
                routing=old_ex.routing,
                bf16_accumulate=old_ex.bf16_accumulate,
                slot_chunk=old_ex._slot_chunk_arg,
                row_unperm=old_ex.row_unperm,
            )
        self = cls.__new__(cls)
        self.mesh = old_ex.mesh
        self.axis = old_ex.axis
        self.n_devices = old_ex.n_devices
        self.sched = new_sched
        self.ktile = old_ex.ktile
        self.bf16_accumulate = old_ex.bf16_accumulate
        self.routing = GATHER
        self._slot_chunk_arg = old_ex._slot_chunk_arg
        self.row_unperm = old_ex.row_unperm
        self._unperm = old_ex._unperm
        self.step_ranges = old_ex.step_ranges
        self._slot_chunk = old_ex._slot_chunk
        self._n_chunks = old_ex._n_chunks

        gcol, tgt, oval = old_ex._host
        val = oval.copy()
        val[slots] = np.asarray(vals, val.dtype)
        self._host = (gcol, tgt, val)
        self._gcol, self._tgt = old_ex._gcol, old_ex._tgt

        k = new_sched.nnz_per_step
        touched_steps = np.unique(np.asarray(slots, np.int64) // k)
        row_len = self._n_chunks * self._slot_chunk
        dirty = [
            bool(np.any((touched_steps >= lo) & (touched_steps < hi)))
            for lo, hi in self.step_ranges
        ]
        devices = list(self.mesh.devices.reshape(-1))
        sharding = NamedSharding(self.mesh, P(self.axis))
        gshape = (self.n_devices, self._n_chunks, self._slot_chunk)
        by_dev = {s.device: s.data for s in old_ex._val.addressable_shards}
        parts = []
        for d, dev in enumerate(devices):
            lo, hi = self.step_ranges[d]
            if not dirty[d]:
                parts.append(by_dev[dev])
                continue
            FAULTS.check("upload", device=dev)
            row = np.zeros((1, row_len), val.dtype)
            row[0, : (hi - lo) * k] = val[lo * k : hi * k]
            parts.append(
                jax.device_put(
                    jnp.asarray(row.reshape(1, self._n_chunks, self._slot_chunk)),
                    dev,
                )
            )
        self._val = jax.make_array_from_single_device_arrays(gshape, sharding, parts)
        self.scoped_upload = True
        self.dirty_devices = int(sum(dirty))
        self.device_bytes = old_ex.device_bytes
        return self


def repaired_executor(old_ex, new_sched: Schedule, repair):
    """Executor for a repaired schedule, reusing ``old_ex``'s device
    buffers wherever the repair (``schedule.repair_schedule``) left steps
    untouched — the scoped re-upload path of DESIGN.md §11.

    Dispatches on the old executor's class; always returns a **new**
    executor object and never mutates ``old_ex``, so
    the serving tier can atomically swap while in-flight batches finish on
    the old one. Guaranteed bit-identical device state to a cold build of
    the same class on ``new_sched`` with the same construction kwargs."""
    if isinstance(old_ex, ShardedScheduleExecutor):
        return ShardedScheduleExecutor._from_repair(old_ex, new_sched, repair)
    if isinstance(old_ex, ScheduleExecutor):
        return ScheduleExecutor._from_repair(old_ex, new_sched, repair)
    raise TypeError(f"unsupported executor type: {type(old_ex).__name__}")


def value_patched_executor(old_ex, new_sched: Schedule, slots, vals):
    """Executor for a schedule produced by ``schedule.value_patch_schedule``
    — structure unchanged, only ``val[slots]`` differ from ``old_ex.sched``.

    The O(|delta|) fast lane of DESIGN.md §11: gcol/tgt device arrays are
    shared with ``old_ex`` and only the changed values are scattered (or
    the dirty ``val`` shards re-uploaded, for the sharded class). Same
    contract as ``repaired_executor``: a new object with bit-identical
    device state to a cold build on ``new_sched``.
    """
    slots = np.asarray(slots, np.int64)
    vals = np.asarray(vals)
    if isinstance(old_ex, ShardedScheduleExecutor):
        return ShardedScheduleExecutor._value_patched(old_ex, new_sched, slots, vals)
    if isinstance(old_ex, ScheduleExecutor):
        return ScheduleExecutor._value_patched(old_ex, new_sched, slots, vals)
    raise TypeError(f"unsupported executor type: {type(old_ex).__name__}")


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
