"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 200 --batch 8 --seq 128

Composes: config → model → mesh/sharding rules → TENSILE memory planning
(remat/offload decisions under a device budget) → data pipeline with
prefetch → resilient train loop (async checkpoints, restart-on-failure,
straggler monitor).  On this container it runs reduced configs on a small
host mesh; the same driver scales to the production mesh on TPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig, Prefetcher, TokenStream
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import MeshRules, use_rules
from repro.launch.steps import (TrainStepConfig, build_train_step,
                                opt_state_for)
from repro.models.registry import get_model
from repro.runtime.fault_tolerance import FTConfig, resilient_train_loop
from repro.runtime.stragglers import StragglerMonitor


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--tensile-budget-mb", type=float, default=0.0,
                    help="device memory budget; >0 runs the TENSILE "
                         "planner and applies its remat decisions")
    ap.add_argument("--grad-compression", choices=["none", "int8"],
                    default="none")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        if cfg.n_experts:
            cfg.moe_impl = "dense" if cfg.n_experts <= 8 else "scatter"
    if args.d_model:
        cfg.d_model = args.d_model
    api = get_model(cfg)

    mesh = make_host_mesh()
    rules = MeshRules(mesh, cfg=cfg)
    print(f"[train] arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)}")

    params, axes = api.init(jax.random.PRNGKey(0))
    use_comp = args.grad_compression == "int8"
    opt = opt_state_for(params)
    if use_comp:
        from repro.optim.adam import adamw_init
        opt = adamw_init(params, grad_compression=True)

    # ---- TENSILE planning (optional) ---------------------------------
    remat_policy = None
    if args.tensile_budget_mb > 0:
        from repro.core import (capture_train_step, schedule_for_budget)
        from repro.core.jax_integration import make_remat_policy
        from repro.optim.adam import adamw_update

        def probe_step(p, o, batch):
            def loss_of(pp):
                return api.loss(pp, batch)
            loss, grads = jax.value_and_grad(loss_of)(p)
            p2, o2 = adamw_update(p, grads, o, lr=args.lr)
            return p2, o2, loss

        dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                          vocab_size=cfg.vocab_size,
                          frontend=cfg.frontend, n_patches=cfg.n_patches,
                          d_model=cfg.d_model, enc_dec=cfg.enc_dec)
        sample = TokenStream(dcfg).batch_at(0)
        seq, _ = capture_train_step(probe_step, params, opt, sample)
        decisions = schedule_for_budget(
            seq, int(args.tensile_budget_mb * 2**20))
        print(f"[tensile] {decisions.summary()}")
        remat_policy = make_remat_policy(decisions)

    tcfg = TrainStepConfig(
        learning_rate=args.lr,
        grad_compression=("int8" if use_comp else None),
        remat_policy=remat_policy)
    step_fn = build_train_step(api, rules, tcfg)
    p_shard = rules.param_shardings(axes)
    with use_rules(rules):
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      vocab_size=cfg.vocab_size, frontend=cfg.frontend,
                      n_patches=cfg.n_patches, d_model=cfg.d_model,
                      enc_dec=cfg.enc_dec)
    stream = TokenStream(dcfg)
    prefetch = Prefetcher(stream)

    monitor = StragglerMonitor(n_hosts=1)
    ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)

    t0 = time.time()
    losses = []

    def logging_step(p, o, batch):
        p, o, m = jitted(p, o, batch)
        losses.append(float(m["loss"]))
        if len(losses) % args.log_every == 0:
            dt = time.time() - t0
            print(f"  step {len(losses):5d} loss {losses[-1]:.4f} "
                  f"({len(losses)/dt:.2f} it/s)")
        return p, o, m

    result = resilient_train_loop(
        logging_step, (params, opt), iter(prefetch), args.steps,
        ft=ft, data_stream=stream, monitor=monitor)
    prefetch.close()
    first = np.mean(losses[:10]) if len(losses) >= 10 else losses[0]
    last = np.mean(losses[-10:])
    print(f"[train] done: steps={result.final_step + 1} "
          f"restarts={result.restarts} loss {first:.4f} -> {last:.4f}")
    assert np.isfinite(last), "training diverged"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
