"""AWB-GCN's rebalancing applied to MoE expert parallelism (DESIGN.md §5).

    PYTHONPATH=src python examples/moe_rebalance.py

Profiles a power-law router load (the MoE analogue of Fig. 5), applies the
AWB placement balancer — remote switching = placement swaps, evil-row
remapping = hot-expert replication — and runs a reduced qwen3-moe layer
with the placement tables, verifying the output is invariant (replicas
compute the same experts; the combine step is the adder tree).
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import moe_balance
from repro.models import moe as moe_mod


def main():
    e, devices = 128, 16
    load = moe_balance.zipf_expert_load(e, 200_000, alpha=1.0, seed=0)
    print(f"router load: top expert holds {load.max() / load.sum():.1%} of "
          f"tokens (power law, {e} experts)")

    static = moe_balance.static_placement(e, devices)
    print(f"static placement imbalance (max/mean device load): "
          f"{moe_balance.imbalance(moe_balance.device_loads(static, load)):.2f}x")
    for spare in (0, 16, 32):
        spd = (e + spare) // devices
        bal = moe_balance.balance_placement(load, devices,
                                            slots_per_device=spd)
        imb = moe_balance.imbalance(moe_balance.device_loads(bal, load))
        print(f"AWB placement, {spare:2d} spare slots: imbalance {imb:.3f}x "
              f"(max replicas {int(bal.replica_count.max())})")

    # run a reduced qwen3-moe MoE layer under the balanced placement
    cfg = configs.get_reduced_config("qwen3-moe-30b-a3b")
    dims = dataclasses.replace  # noqa: F841  (kept simple below)
    mdims = moe_mod.MoEDims(cfg.d_model, 32, 8, 2, capacity_factor=64.0,
                            n_slots=12)
    params = moe_mod.init_moe_params(jax.random.PRNGKey(0), mdims)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    load8 = moe_balance.zipf_expert_load(8, 10_000, alpha=1.0, seed=2)
    placement = moe_balance.balance_placement(load8, 4, slots_per_device=3)
    tables = moe_mod.tables_from_placement(placement)
    out_bal, _ = moe_mod.moe_forward(params, mdims, x, placement=tables)
    out_ref, _ = moe_mod.moe_forward(params, mdims, x)
    err = float(jnp.abs(out_bal - out_ref).max())
    print(f"\nMoE layer output under AWB placement vs identity: "
          f"max err {err:.2e} (replicas are exact)")
    assert err < 1e-4
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
