"""AWB-balanced SpMM Pallas TPU kernel.

Consumes a ``core.schedule.Schedule`` (the converged AWB configuration) and
computes ``C = A @ B`` for sparse A, dense B.

TPU adaptation of the paper's engine (DESIGN.md §2):

* A *step* (one grid iteration) is the analogue of a PE's round of work:
  exactly ``nnz_per_step`` non-zero slots, VMEM-resident.
* Routing non-zeros to PEs (the paper's omega network) has two TPU
  realizations, selected per operand by ``core.executor``'s cost model:

  - ``"onehot"``: two **one-hot matmuls on the MXU** — gathering B rows is
    ``one_hot(local_col) @ B_block`` and scattering into the window
    accumulator is ``one_hot(local_row).T @ contributions``. Dynamic routing
    as dense contractions; the MXU retires a step in ~(K·CB + K·R)·ktile/16K
    cycles. Viable only when ``cols_per_block`` is capped (schedule built
    with ``cols_per_block="auto"``) so the [K, CB] routing matrix stays a
    couple of MXU tiles instead of spanning the whole matrix width.
  - ``"gather"``: a dynamic **VPU gather** of B rows by slot index
    (``b_block[local_col]``) followed by the same one-hot scatter. Routing
    work scales with K alone. Interpret mode only: Mosaic does not lower
    the in-kernel ``jnp.take``, so compiled kernels route one-hot and the
    served path's gather routing is the XLA executor body.

* The window accumulator lives in the output block; steps of one window are
  consecutive (schedule contract), so it is zeroed on window entry and
  written back once per window — the ACC-buffer of the paper with RaW
  hazards resolved by construction.
* Evil-row chunks land in private trailing-window slots; the host-side
  ``scatter_epilogue`` is the Labor-PE adder tree.

Grid: ``(n_ktiles, n_steps)`` with the k dimension parallel (megacore) and
steps sequential ("arbitrary") because consecutive steps share accumulator
state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import csc as _fmt
from repro.core.schedule import Schedule


def _kernel(win_ref, cblk_ref,            # scalar prefetch
            val_ref, lrow_ref, lcol_ref,  # [1, K] step slots
            b_ref,                        # [CB, ktile] dense block
            out_ref,                      # [R, ktile] window accumulator
            *, n_rows_window: int, acc_dtype, routing: str):
    step = pl.program_id(1)

    # window entry: previous step belonged to a different window (or first)
    prev = jnp.maximum(step - 1, 0)
    is_first = jnp.logical_or(step == 0, win_ref[step] != win_ref[prev])

    @pl.when(is_first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    k = val_ref.shape[-1]
    cb = b_ref.shape[0]

    val = val_ref[0, :].astype(acc_dtype)           # [K]
    lcol = lcol_ref[0, :]                           # [K]
    lrow = lrow_ref[0, :]                           # [K]

    if routing == "onehot":
        # gather B rows via one-hot contraction (the omega network as a
        # dense MXU contraction — [K, CB] must be capped to stay cheap).
        # HIGHEST: at the MXU's default precision an f32 operand is rounded
        # to bfloat16, and the selection would no longer be exact
        gather = (lcol[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (k, cb), 1)).astype(acc_dtype)       # [K, CB]
        rows = jax.lax.dot(gather, b_ref[...].astype(acc_dtype),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=acc_dtype)  # [K, ktile]
    else:
        # dynamic VPU gather by slot index: routing work scales with K
        rows = jnp.take(b_ref[...], lcol, axis=0).astype(acc_dtype)
    contrib = rows * val[:, None]

    # scatter-accumulate into the window via one-hot^T contraction (R is
    # small, so this contraction is cheap on both routing paths)
    scatter = (lrow[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (k, n_rows_window), 1)).astype(acc_dtype)  # [K, R]
    acc = jax.lax.dot(scatter.T, contrib,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=acc_dtype)        # [R, ktile]
    out_ref[...] += acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "k", "r", "cb", "n_windows", "ktile", "interpret", "routing"))
def _spmm_pallas_perm(val, lrow, lcol, win, cblk, b,
                      *, k: int, r: int, cb: int, n_windows: int,
                      ktile: int, interpret: bool, routing: str):
    n, kdim = b.shape
    n_steps = win.shape[0]
    if not interpret:
        # no-op tail steps (val 0, last window: they accumulate nothing)
        # to a multiple of 1024. Mosaic's compile time and code size blow
        # up on scalar-prefetch arrays of other lengths: at 42,437 steps
        # 16 s and 16.6 MB of code, at 43,008 steps 0.8 s and 0.7 MB
        # (compiled for a described v5e)
        pad_s = (-n_steps) % 1024
        win = jnp.pad(win, (0, pad_s), mode="edge")
        cblk = jnp.pad(cblk, (0, pad_s))
        val, lrow, lcol = (jnp.pad(x, (0, pad_s * k)) for x in (val, lrow, lcol))
        n_steps += pad_s

    pad_k = (-kdim) % ktile
    bp = jnp.pad(b, ((0, (-n) % cb), (0, pad_k)))
    kd = kdim + pad_k

    grid = (kd // ktile, n_steps)
    out_shape = jax.ShapeDtypeStruct((n_windows * r, kd), b.dtype)
    # step slots live as [n_steps, 1, K] with the step dim squeezed out of
    # the block: Mosaic needs a block's last two dims to be (8, 128)
    # multiples or the full extents, and (1, K) is the full extent
    slot_spec = pl.BlockSpec((pl.Squeezed(), 1, k),
                             lambda j, i, win, cblk: (i, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, n_rows_window=r, acc_dtype=jnp.float32,
                          routing=routing),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                slot_spec, slot_spec, slot_spec,
                pl.BlockSpec((cb, ktile),
                             lambda j, i, win, cblk: (cblk[i], j)),
            ],
            out_specs=pl.BlockSpec((r, ktile),
                                   lambda j, i, win, cblk: (win[i], j)),
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(win, cblk, val.reshape(n_steps, 1, k), lrow.reshape(n_steps, 1, k),
      lcol.reshape(n_steps, 1, k), bp)
    return out[:, :kdim]


def spmm_balanced(sched: Schedule, b: jax.Array, *, ktile: int = 128,
                  interpret: bool | None = None,
                  routing: str = "auto") -> jax.Array:
    """C = A @ B through the AWB schedule.

    ``interpret`` defaults to the backend: compiled by Mosaic on a TPU,
    the interpreter (validation mode) everywhere else. ``routing`` is
    ``"onehot"`` (MXU dense routing), ``"gather"`` (VPU dynamic gather —
    interpret mode only: Mosaic cannot lower an in-kernel ``jnp.take``
    over a VMEM block), or ``"auto"``: the executor cost model decides
    from the schedule's K/CB/R geometry in interpret mode, and compiled
    kernels take the one routing that lowers, ``"onehot"``.
    """
    from repro.core.executor import GATHER, ONEHOT, device_step_arrays, \
        select_routing
    from repro.core.schedule import scatter_epilogue

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if routing == "auto":
        routing = select_routing(sched.nnz_per_step, sched.cols_per_block,
                                 sched.rows_per_window, ktile) \
            if interpret else ONEHOT
    if routing == GATHER and not interpret:
        raise ValueError(
            "routing='gather' runs only with interpret=True: its in-kernel "
            "dynamic row gather does not lower through Mosaic; use "
            "routing='onehot' (or 'auto') for the compiled kernel")
    # device-resident copies of the schedule arrays, uploaded once per
    # schedule (shared with one-hot executors) — repeated calls move no
    # schedule bytes
    steps = device_step_arrays(sched)
    out_perm = _spmm_pallas_perm(
        steps["val"].reshape(-1), steps["lrow"].reshape(-1),
        steps["lcol"].reshape(-1), steps["win"], steps["cblk"], b,
        k=sched.nnz_per_step, r=sched.rows_per_window,
        cb=sched.cols_per_block, n_windows=sched.n_windows,
        ktile=ktile, interpret=interpret, routing=routing)
    return scatter_epilogue(sched, out_perm)


# ---------------------------------------------------------------------------
# Differentiable wrapper: d(A@B)/dB = Aᵀ @ dC, served by a second schedule
# built for Aᵀ (the graph is static, so both schedules amortize like the
# paper's converged configuration). A's values are treated as constants
# (the normalized adjacency is not trained).
# ---------------------------------------------------------------------------


def transpose_coo(a: "_fmt.COO") -> "_fmt.COO":
    return _fmt.transpose_coo(a)


def make_spmm_fn(a: "_fmt.COO", *, nnz_per_step: int = 256,
                 rows_per_window: int = 64, ktile: int = 128,
                 interpret: bool | None = None,
                 schedules: tuple[Schedule, Schedule] | None = None,
                 routing: str = "auto"):
    """Returns a differentiable ``f(b) = A @ b`` backed by the Pallas kernel
    with schedules for A and Aᵀ built once (the converged configurations).

    ``schedules`` accepts a prebuilt ``(forward, transpose)`` pair; when
    omitted, both come from the executor's fingerprint cache
    (``executor.get_spmm_schedules``), so repeated call sites on the same
    graph share one build instead of re-running it.
    """
    if schedules is None:
        from repro.core.executor import get_spmm_schedules
        schedules = get_spmm_schedules(a, nnz_per_step=nnz_per_step,
                                       rows_per_window=rows_per_window)
    sched, sched_t = schedules

    @jax.custom_vjp
    def f(b):
        return spmm_balanced(sched, b, ktile=ktile, interpret=interpret,
                             routing=routing)

    def fwd(b):
        return f(b), None

    def bwd(_, dc):
        return (spmm_balanced(sched_t, dc, ktile=ktile,
                              interpret=interpret, routing=routing),)

    f.defvjp(fwd, bwd)
    return f
