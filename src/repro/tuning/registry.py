"""In-process caches: graph fingerprint → schedule / executor.

``core.executor`` used to own these dicts; they live here now so the
executor module is purely the execution machinery and every caching policy
(in-process here, on-disk in ``tuning.store``) sits in one subsystem.

The fingerprint-keyed caches are deliberately unbounded: a serving system
holds a handful of long-lived graphs, and the converged configuration is
exactly what must persist (bounded rotation across *thousands* of graphs is
the serving engine's job — ``serving.gcn_engine`` evicts device-resident
schedules under an LRU byte budget and bypasses these caches). The
identity-keyed per-schedule cache is a bounded LRU — workloads that build
throwaway schedules per call must not retain every one forever.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from repro.core import csc as fmt
from repro.core import executor as _exe
from repro.core import reorder as _reorder
from repro.core import schedule as _schedule
from repro.core.executor import (
    ExecutionConfig,
    _ExecutorBase,
    make_executor,
    select_routing,
)
from repro.core.schedule import Schedule


def graph_fingerprint(a: fmt.COO) -> str:
    """Content hash of a sparse operand — the schedule-cache key and the
    graph half of the on-disk store key.

    Hashes shape, true nnz, and the index/value bytes of real (non-PAD)
    entries, so two COOs describing the same matrix — padded or not — map
    to the same converged configuration.
    """
    row = np.asarray(a.row)
    col = np.asarray(a.col)
    val = np.asarray(a.val)
    if (row == fmt.PAD_IDX).any():
        keep = row != fmt.PAD_IDX
        row, col, val = row[keep], col[keep], val[keep]
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((a.shape, int(row.shape[0]))).encode())
    h.update(row.tobytes())
    h.update(col.tobytes())
    h.update(val.tobytes())
    return h.hexdigest()


def delta_fingerprint(parent_fp: str, delta, revision: int) -> str:
    """Chained identity of a streamed graph mutation: the parent's
    fingerprint hashed with the edge delta's bytes and the repair
    generation. O(|delta|) instead of the O(nnz) full-content hash — the
    streaming path's cheap lineage identity for logging and in-memory
    bookkeeping. Two graphs reached by the same delta sequence share it;
    unlike ``graph_fingerprint`` it is *not* content-canonical (different
    delta orders reaching the same matrix hash differently), so on-disk
    store entries keep using the content hash."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent_fp.encode())
    h.update(repr(int(revision)).encode())
    h.update(np.asarray(delta.row).tobytes())
    h.update(np.asarray(delta.col).tobytes())
    h.update(np.asarray(delta.val).tobytes())
    return h.hexdigest()


def mesh_fingerprint(mesh=None, n_devices: Optional[int] = None):
    """Hashable identity of the requested device mesh — the second half of
    the ``(graph fingerprint, mesh)`` executor-cache key.

    ``None`` (no mesh, no device count) means the plain single-device
    ``ScheduleExecutor``; ``n_devices=1`` is a *distinct* entry (a 1-device
    sharded executor), so single- and multi-device executors coexist in the
    cache. Device ids are part of the key: the same shape on different
    devices is a different placement.
    """
    import jax

    if mesh is None and n_devices is None:
        return None
    if mesh is not None:
        if n_devices is not None and n_devices != mesh.devices.size:
            raise ValueError(
                f"n_devices={n_devices} contradicts the given mesh of "
                f"{mesh.devices.size} device(s); pass one or the other"
            )
        return (
            tuple(mesh.axis_names),
            tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat),
        )
    devs = jax.devices()
    if not 1 <= n_devices <= len(devs):
        raise ValueError(
            f"n_devices={n_devices} but this host exposes "
            f"{len(devs)} device(s)"
        )
    devs = devs[:n_devices]
    return (("dev",), (len(devs),), tuple(int(d.id) for d in devs))


def device_fingerprint(device) -> Optional[tuple]:
    """Hashable identity of a single-device placement handle — the third
    leg of the ``(graph fingerprint, mesh, device)`` executor-cache key.
    ``None`` (jax's default placement) stays ``None``, so existing
    un-pinned entries keep their keys; a pinned handle keys by platform +
    device id, letting **same-graph replicas on different devices
    coexist** in the cache instead of the last-built replica evicting the
    others."""
    if device is None:
        return None
    return (str(getattr(device, "platform", "?")), int(device.id))


_SCHEDULE_CACHE: dict = {}
_EXECUTOR_CACHE: dict = {}
_REORDER_CACHE: dict = {}
_EXEC_BY_SCHEDULE: "OrderedDict[tuple, _ExecutorBase]" = OrderedDict()
_EXEC_BY_SCHEDULE_CAP = 32


def clear_caches() -> None:
    """Drop every cached schedule/executor/tuning result (tests; also the
    closest thing to simulating a process restart in-process)."""
    from repro.tuning import runner

    _SCHEDULE_CACHE.clear()
    _EXECUTOR_CACHE.clear()
    _REORDER_CACHE.clear()
    _EXEC_BY_SCHEDULE.clear()
    _exe._DEVICE_STEPS.clear()
    runner._AUTOTUNE_CACHE.clear()


def _sched_key(
    fp: str,
    nnz_per_step,
    rows_per_window,
    cols_per_block,
    window_nnz,
    balanced,
    reorder="none",
):
    return (
        fp,
        nnz_per_step,
        rows_per_window,
        str(cols_per_block),
        window_nnz,
        balanced,
        reorder,
    )


def get_reorder(a: fmt.COO, strategy: str, fingerprint: Optional[str] = None):
    """Fingerprint-cached ``(perm, inv)`` for one reorder strategy
    (``core.reorder``) — the permutation is a pure function of graph
    content, so every schedule/executor variant of a graph shares one
    computation. ``(None, None)`` for ``"none"``."""
    if strategy == _reorder.REORDER_NONE:
        return None, None
    fp = fingerprint or graph_fingerprint(a)
    key = (fp, strategy)
    pair = _REORDER_CACHE.get(key)
    if pair is None:
        pair = _reorder.permutation(a, strategy)
        _REORDER_CACHE[key] = pair
    return pair


def adopt_reorder(fingerprint: str, strategy: str, perm: np.ndarray) -> None:
    """Seed the reorder cache with a store entry's persisted permutation,
    so the adopted schedule and the executor's un-permute stay consistent
    even when a fresh recompute would order ties differently (a repaired
    permutation persisted by serving is one such case — any valid
    permutation consistent with the adopted schedule is correct)."""
    if strategy == _reorder.REORDER_NONE or perm is None:
        return
    inv = _reorder.invert_permutation(perm)
    _REORDER_CACHE.setdefault(
        (fingerprint, strategy), (np.asarray(perm, np.int32), inv)
    )


def release_graph(fingerprint: str) -> None:
    """Drop every cached schedule/executor of one graph.

    The fingerprint caches are deliberately unbounded for long-lived
    serving graphs; a caller that sweeps *many* configurations of a graph
    it will not serve through the registry (the serving engine's cold
    autotune measures ~a dozen device-resident candidate executors) calls
    this afterwards so the sweep's uploads don't pin device memory
    forever."""
    for key in [k for k in _SCHEDULE_CACHE if k[0] == fingerprint]:
        # also drop the schedule's memoized device step arrays (one-hot
        # executors share them through the executor module's LRU)
        _exe.release_device_steps(_SCHEDULE_CACHE.pop(key))
    for key in [k for k in _EXECUTOR_CACHE if k[0][0] == fingerprint]:
        del _EXECUTOR_CACHE[key]
    for key in [k for k in _REORDER_CACHE if k[0] == fingerprint]:
        del _REORDER_CACHE[key]


def get_schedule(
    a: fmt.COO,
    *,
    nnz_per_step: int = 256,
    rows_per_window: int = 64,
    cols_per_block=None,
    window_nnz: Optional[int] = None,
    balanced: bool = True,
    reorder: str = "none",
    fingerprint: Optional[str] = None,
) -> Schedule:
    """Fingerprint-cached schedule build — the 'reuse the converged
    configuration' entry point.

    ``reorder`` selects a locality row remapping (``core.reorder``): the
    schedule is built on the row-permuted graph, and the matching executor
    (``get_executor`` with the same ``reorder``) un-permutes outputs so
    callers see original row order."""
    fp = fingerprint or graph_fingerprint(a)
    key = _sched_key(
        fp, nnz_per_step, rows_per_window, cols_per_block, window_nnz, balanced, reorder
    )
    sched = _SCHEDULE_CACHE.get(key)
    if sched is None:
        if reorder != _reorder.REORDER_NONE:
            perm, _ = get_reorder(a, reorder, fingerprint=fp)
            a = fmt.permute_coo(a, perm)
        if balanced:
            sched = _schedule.build_balanced_schedule(
                a,
                nnz_per_step,
                rows_per_window,
                cols_per_block=cols_per_block,
                window_nnz=window_nnz,
            )
        else:
            sched = _schedule.build_naive_schedule(
                a, nnz_per_step, rows_per_window, cols_per_block=cols_per_block
            )
        _SCHEDULE_CACHE[key] = sched
    return sched


def adopt_schedule(fingerprint: str, cfg, sched: Schedule) -> None:
    """Seed the schedule cache with a deserialized store entry, so the
    subsequent ``get_executor(a, **cfg.as_executor_kwargs())`` is a pure
    cache hit — **zero** ``build_balanced_schedule`` calls on the
    warm-start path."""
    key = _sched_key(
        fingerprint,
        cfg.nnz_per_step,
        cfg.rows_per_window,
        cfg.cols_per_block,
        cfg.window_nnz,
        True,
        getattr(cfg, "reorder", "none"),
    )
    _SCHEDULE_CACHE.setdefault(key, sched)


def get_spmm_schedules(
    a: fmt.COO,
    *,
    nnz_per_step: int = 256,
    rows_per_window: int = 64,
    cols_per_block=None,
) -> Tuple[Schedule, Schedule]:
    """(schedule for A, schedule for Aᵀ), both fingerprint-cached — what a
    differentiable SpMM needs (d(A@B)/dB = Aᵀ @ dC). Call sites stop
    rebuilding both schedules per invocation."""
    fwd = get_schedule(
        a,
        nnz_per_step=nnz_per_step,
        rows_per_window=rows_per_window,
        cols_per_block=cols_per_block,
    )
    a_t = fmt.transpose_coo(a)
    bwd = get_schedule(
        a_t,
        nnz_per_step=nnz_per_step,
        rows_per_window=rows_per_window,
        cols_per_block=cols_per_block,
    )
    return fwd, bwd


def _placement_key(mesh, n_devices, device):
    """(mesh fingerprint, device fingerprint) with the combination rules:
    ``device`` pins a single-device executor, so it contradicts a mesh."""
    if device is not None and (mesh is not None or n_devices is not None):
        raise ValueError(
            "device= pins a single-device executor to one placement; it "
            "cannot be combined with n_devices/mesh"
        )
    return mesh_fingerprint(mesh, n_devices), device_fingerprint(device)


def get_executor(
    a: fmt.COO,
    *,
    nnz_per_step: int = 256,
    rows_per_window: int = 64,
    cols_per_block=None,
    window_nnz: Optional[int] = None,
    ktile: int = 128,
    routing: Optional[str] = None,
    balanced: bool = True,
    bf16_accumulate: bool = False,
    n_devices: Optional[int] = None,
    mesh=None,
    device=None,
    reorder: str = "none",
) -> _ExecutorBase:
    """Fingerprint-cached executor: the first call converges (builds the
    schedule, uploads it); every later call with the same graph + config is
    a pure cache hit — no rebuild, no host→device transfer.

    Pass ``n_devices`` (or a 1-D ``mesh``) for a ``ShardedScheduleExecutor``
    whose schedule shards live one-per-device, or ``device`` (a
    ``jax.Device``) for a ``ScheduleExecutor`` pinned to one mesh device.
    The cache keys on ``(graph fingerprint, mesh, device)``, so single-,
    multi-device, and per-replica executors of the same graph coexist.
    """
    fp = graph_fingerprint(a)
    mkey, dkey = _placement_key(mesh, n_devices, device)
    key = (
        _sched_key(
            fp,
            nnz_per_step,
            rows_per_window,
            cols_per_block,
            window_nnz,
            balanced,
            reorder,
        ),
        ktile,
        routing,
        bf16_accumulate,
        mkey,
        dkey,
    )
    ex = _EXECUTOR_CACHE.get(key)
    if ex is None:
        sched = get_schedule(
            a,
            nnz_per_step=nnz_per_step,
            rows_per_window=rows_per_window,
            cols_per_block=cols_per_block,
            window_nnz=window_nnz,
            balanced=balanced,
            reorder=reorder,
            fingerprint=fp,
        )
        _, inv = get_reorder(a, reorder, fingerprint=fp)
        ex = make_executor(
            sched,
            ExecutionConfig(ktile, routing, bf16_accumulate),
            device=device,
            mesh=mesh,
            n_devices=n_devices,
            row_unperm=inv,
        )
        _EXECUTOR_CACHE[key] = ex
    return ex


def executor_for_schedule(
    sched: Schedule,
    *,
    ktile: int = 128,
    routing: Optional[str] = None,
    bf16_accumulate: bool = False,
    n_devices: Optional[int] = None,
    mesh=None,
    device=None,
) -> _ExecutorBase:
    """Executor for a caller-built schedule, memoized per (schedule
    instance, ktile, routing, mesh, device) — identity-keyed, so
    rebuilding a schedule re-uploads while reusing one doesn't, and
    asking for a different routing/ktile/mesh/device never returns a
    mismatched cached executor."""
    routing = routing or select_routing(
        sched.nnz_per_step, sched.cols_per_block, sched.rows_per_window, ktile
    )
    mkey, dkey = _placement_key(mesh, n_devices, device)
    key = (id(sched), ktile, routing, bf16_accumulate, mkey, dkey)
    ex = _EXEC_BY_SCHEDULE.get(key)
    if ex is not None and ex.sched is sched:
        _EXEC_BY_SCHEDULE.move_to_end(key)
        return ex
    ex = make_executor(
        sched,
        ExecutionConfig(ktile, routing, bf16_accumulate),
        device=device,
        mesh=mesh,
        n_devices=n_devices,
    )
    _EXEC_BY_SCHEDULE[key] = ex
    if len(_EXEC_BY_SCHEDULE) > _EXEC_BY_SCHEDULE_CAP:
        _EXEC_BY_SCHEDULE.popitem(last=False)
    return ex
