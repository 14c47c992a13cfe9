"""Run TENSILE's two main paths once on one TPU chip, at TinyLlama-1.1B's
published widths (22 layers, d_model 2048, 32/4 heads, d_ff 5632, vocab
32000, bf16) with random weights made from ``--seed``.

    PYTHONPATH=src python chip_smoke.py --seed 0

Phases, all in this one process:

1. train  — a TinyLlama train step (``api.loss`` + ``adamw_update``) goes
   through ``GlobalController.submit`` twice: unscheduled (the ``vanilla``
   pipeline, the device size as budget), then under the ``tensile``
   pipeline at a budget below the vanilla peak, so the plan swaps.  The
   scheduled losses must be bit-identical to the vanilla ones.
2. serve  — ``ServingEngine`` serves a burst of requests without a KV
   budget and with one of about two slots' KV (batched KV transfers); the
   token ids must match, and prefill through the cache must agree with
   ``api.forward`` on the last prompt position's logits.
3. kernels — the Pallas kernels of the main path, compiled for the chip,
   against their ``kernels/ref.py`` oracles.

Findings go to earlier lines.  The last line is one JSON object naming the
device; it is printed only when every phase passed.  A failed phase
prints its traceback and the next phase still runs; the exit code is
then 1.  Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "tinyllama-1.1b"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_ITERS, TRAIN_LR = 1, 512, 3, 1e-4
# the scheduled run's budget, as a share of the vanilla peak
BUDGET_FRACTION = 0.8
# device bytes a freed job may leave behind (loaded programs, allocator)
FREED_SLACK = 256 * 2 ** 20
SERVE_SLOTS, SERVE_REQUESTS, PROMPT, GEN = 4, 8, 64, 32
# relative L2 error allowed between prefill-through-the-cache and the full
# forward on the last position's bf16 logits.  A bf16 model of the same
# depth (22 layers, d_model 512, 3 seeds) differed by 0.045-0.078 between
# the two paths on the CPU; a wrong cache position or mask gives ~1.
LOGITS_REL_TOL = 0.2
# a full-width MLP weight (d_model x d_ff) for the offload-quant kernels
QUANT_SHAPE = (2048, 5632)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def gib(n: float) -> str:
    return f"{n / 2 ** 30:.3f} GiB"


def device_bytes(dev, key: str) -> int:
    stats = dev.memory_stats()
    if not stats or key not in stats:
        raise RuntimeError(f"{dev.device_kind} reports no {key!r} "
                           f"in memory_stats(): {stats}")
    return int(stats[key])


# ----------------------------------------------------------------------
# 1. training through the TENSILE controller
# ----------------------------------------------------------------------
def train_phase(cfg, profile, dev, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import GlobalController, SchedulerConfig
    from repro.core.cost_model import CostModel, DeviceCalibration
    from repro.core.peak_analysis import analyze
    from repro.models.registry import get_model
    from repro.optim.adam import adamw_init, adamw_update
    from repro.service import JobSpec

    api = get_model(cfg)
    batch, seq_len, iters = TRAIN_BATCH, TRAIN_SEQ, TRAIN_ITERS

    def train_step(params, opt_state, data):
        # launch/train.py's probe step: loss, grads, one AdamW update
        loss, grads = jax.value_and_grad(lambda p: api.loss(p, data))(params)
        params, opt_state = adamw_update(params, grads, opt_state,
                                         lr=TRAIN_LR)
        return params, opt_state, loss

    def payload():
        params, _ = api.init(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                    (batch, seq_len + 1), 0, cfg.vocab_size)
        data = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
        return train_step, params, adamw_init(params), data

    def run(pipeline: str, budget: int):
        # capture-time latencies start from the device's published peaks
        cost = CostModel(DeviceCalibration(flops=profile.compute_flops,
                                           mem_bw=profile.mem_bw))
        ctl = GlobalController(
            profile=profile, cost_model=cost, pipeline_name=pipeline,
            scheduler_config=SchedulerConfig(memory_budget_bytes=budget))
        t0 = time.perf_counter()
        handle = ctl.submit(JobSpec(f"train-{pipeline}", iterations=iters,
                                    payload=payload()))
        ctl.wait()
        wall = time.perf_counter() - t0
        losses = [np.asarray(out[0]) for out in handle.outputs]
        check(len(losses) == iters, f"{pipeline}: {iters} losses")
        check(all(np.isfinite(x) for x in losses),
              f"{pipeline}: finite losses {losses}")
        swaps = sum(s.swap_out_count for s in handle.stats)
        recomputes = sum(s.recompute_count for s in handle.stats)
        # the executor frees every tensor at its last use, so without a
        # plan the peak it reaches is the step's free-at-last-use peak
        free_peak = analyze([handle.seq]).peak_bytes
        plan = handle.plan
        log(f"[train] {pipeline}: budget {gib(budget)}; free-at-last-use "
            f"peak {gib(free_peak)}; the plan's own peaks: planned "
            f"{gib(plan.planned_peak_bytes)}, vanilla "
            f"{gib(plan.vanilla_peak_bytes)}; ledger peak "
            f"{gib(handle.peak_bytes)}; device peak_bytes_in_use so far "
            f"{gib(device_bytes(dev, 'peak_bytes_in_use'))}")
        log(f"[train] {pipeline}: swap-outs {swaps}, recomputes {recomputes}"
            f", losses {[float(x) for x in losses]}, step times (s) "
            f"{[round(t, 3) for t in handle.step_times]}, wall {wall:.1f} s")
        del handle, ctl
        gc.collect()
        return losses, swaps, free_peak

    baseline = device_bytes(dev, "bytes_in_use")
    log(f"[train] {cfg.name}: {cfg.param_count() / 1e9:.3f} B params, "
        f"batch {batch}x{seq_len}, {iters} iterations; device bytes in use "
        f"before {gib(baseline)}")

    ref_losses, _, vanilla_peak = run("vanilla", profile.device_memory_bytes)
    left = device_bytes(dev, "bytes_in_use") - baseline
    log(f"[train] vanilla job freed: {left} bytes above the baseline")
    check(left <= FREED_SLACK, f"freed job leaves {left} bytes on device")

    budget = int(BUDGET_FRACTION * vanilla_peak)
    losses, swaps, _ = run("tensile", budget)
    check(swaps > 0, "the tensile plan swapped")
    check(all(a.tobytes() == b.tobytes() for a, b in zip(losses, ref_losses)),
          f"scheduled losses {losses} bit-identical to vanilla {ref_losses}")
    log("[train] ok: scheduled losses are bit-identical to vanilla")


# ----------------------------------------------------------------------
# 2. serving through ServingEngine
# ----------------------------------------------------------------------
def serve_phase(profile, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import MemoryEngine
    from repro.serving import ServingEngine
    from repro.serving.traces import make_trace

    t0 = time.perf_counter()
    eng = ServingEngine(ARCH, reduced=False, max_sequences=SERVE_SLOTS,
                        max_len=PROMPT + GEN, seed=seed)
    log(f"[serve] {eng.cfg.name}: {SERVE_SLOTS} slots, prompt {PROMPT}, "
        f"gen {GEN}, mesh {dict(eng.rules.mesh.shape)}, "
        f"{eng.bytes_per_token} KV bytes/token")
    requests = make_trace("burst", SERVE_REQUESTS, seed=seed,
                          prompt_len=PROMPT, gen_len=GEN)

    ref_rep, ref_out = eng.serve(requests, budget_bytes=None)
    t1 = time.perf_counter()
    budget = eng.bytes_per_token * ((PROMPT + GEN) * 2 + 2)
    mem = MemoryEngine(profile=profile, capacity_bytes=budget, trace=True)
    rep, out = eng.serve(requests, budget_bytes=budget, engine=mem,
                         batch_transfers=True)
    t2 = time.perf_counter()
    for name, r in (("unbudgeted", ref_rep), ("budgeted", rep)):
        log(f"[serve] {name}: served {r.served}/{r.n_requests}, "
            f"{r.tokens_generated} tokens, evictions {r.evictions}, "
            f"prefetches {r.prefetches}, batched transfers "
            f"{r.batched_transfers}, ledger peak {r.peak_bytes} B")
    log(f"[serve] wall: engine + unbudgeted {t1 - t0:.1f} s, "
        f"budgeted ({budget} B) {t2 - t1:.1f} s")
    check(ref_rep.served == SERVE_REQUESTS, "all requests served unbudgeted")
    check(rep.served == SERVE_REQUESTS, "all requests served budgeted")
    check(all(len(t) == GEN for t in ref_out.values()),
          f"{GEN} tokens per request")
    check(rep.evictions > 0, "the budgeted run evicted")
    check(rep.batched_transfers > 0, "KV moved through batched transfers")
    check(out == ref_out, "budgeted token ids equal the unbudgeted ones")

    prompt = eng.prompt_for("logits-check", PROMPT)
    got = np.asarray(eng.prefill(prompt).logits, np.float32)
    full, _ = jax.jit(eng.api.forward)(eng.params,
                                       {"tokens": jnp.asarray(prompt)[None]})
    want = np.asarray(full[0, -1], np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    log(f"[serve] prefill vs forward, last-position logits: relative L2 "
        f"{rel:.5f} (tolerance {LOGITS_REL_TOL}), max abs "
        f"{float(np.abs(got - want).max()):.5f}, argmax "
        f"{int(got.argmax())} vs {int(want.argmax())}")
    check(np.all(np.isfinite(got)), "finite prefill logits")
    check(rel <= LOGITS_REL_TOL, "prefill logits agree with the forward")
    log("[serve] ok: both runs served every request with equal token ids")
    return eng


# ----------------------------------------------------------------------
# 3. the Pallas kernels of the main path, compiled
# ----------------------------------------------------------------------
def kernel_phase(eng, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.backend import interpret_mode, platform_of
    from repro.kernels.kv_block_copy import kv_block_gather, kv_block_scatter
    from repro.kernels.offload_quant import (dequantize_blocked,
                                             quantize_blocked)

    leaves = jax.tree_util.tree_leaves(eng.cache)
    spec = next(s for s in eng._axes if s.batch is not None)
    leaf = leaves[eng._axes.index(spec)]
    on_chip = platform_of(leaf) == "tpu"
    log(f"[kernels] cache leaf {leaf.shape} {leaf.dtype} on "
        f"{platform_of(leaf)}: interpret mode {interpret_mode(leaf)}")
    check(interpret_mode(leaf) is not on_chip, "compiled on a TPU only")
    key = jax.random.PRNGKey(seed + 2)
    pool = jax.random.normal(key, leaf.shape, jnp.float32).astype(leaf.dtype)
    idx = jnp.asarray([2, 0], jnp.int32)
    x = (jax.random.normal(key, QUANT_SHAPE, jnp.float32) * 3).astype(
        jnp.bfloat16)
    if on_chip:
        # a kernel compiled to Mosaic is a custom call in the program; in
        # interpret mode it would be plain XLA ops
        for name, fn, arg in (
                ("kv_block_gather",
                 lambda p: kv_block_gather(p, idx, axis=spec.batch), pool),
                ("quantize_blocked", lambda v: quantize_blocked(v)[:2], x)):
            text = jax.jit(fn).lower(arg).compile().as_text()
            check("tpu_custom_call" in text, f"{name} compiled to Mosaic")
        log("[kernels] kv_block_gather and quantize_blocked are Mosaic "
            "custom calls in their compiled programs")
    rows = kv_block_gather(pool, idx, axis=spec.batch)
    check(bool(jnp.array_equal(
        rows, ref.kv_block_gather_ref(pool, idx, spec.batch))),
        f"kv_block_gather on {leaf.shape} equals its oracle")
    new = jnp.flip(rows, axis=spec.batch)
    want = ref.kv_block_scatter_ref(pool, idx, new, spec.batch)
    check(bool(jnp.array_equal(
        kv_block_scatter(pool, idx, new, axis=spec.batch), want)),
        f"kv_block_scatter on {leaf.shape} equals its oracle")

    q, s, meta = quantize_blocked(x)
    q_ref, s_ref, _ = ref.quantize_blocked_ref(np.asarray(x))
    steps = np.abs(np.asarray(q, np.int32) - q_ref.astype(np.int32))
    log(f"[kernels] quantize {QUANT_SHAPE} bf16: {int((steps > 0).sum())} of "
        f"{steps.size} int8 codes one step off the oracle (rounding ties)")
    check(int(steps.max()) <= 1, "int8 codes within one step of the oracle")
    check(np.allclose(np.asarray(s), s_ref, rtol=1e-6), "scales match")
    back = dequantize_blocked(q, s, meta)
    want = ref.dequantize_blocked_ref(np.asarray(q), np.asarray(s), meta)
    check(np.array_equal(np.asarray(back, np.float32),
                         np.asarray(want, np.float32)),
          "dequantize equals its oracle")
    log("[kernels] ok: KV gather/scatter and offload quantization match "
        "their oracles")


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX reports {dev.platform!r} "
              f"({dev.device_kind}). Nothing was run.", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.core.plan import DEVICE_PEAKS, MachineProfile
    from repro.launch.compile_cache import setup_compile_cache
    from repro.launch.mesh import make_host_mesh

    log(f"[device] {dev.platform} {dev.device_kind!r}, "
        f"{len(jax.devices())} device(s), jax {jax.__version__}")
    log(f"[device] compile cache: {setup_compile_cache()}")
    profile = MachineProfile.for_device(dev)
    row = DEVICE_PEAKS[dev.device_kind]
    log(f"[device] profile row {dev.device_kind!r} ({row['name']}): "
        f"{profile.compute_flops:.3g} FLOP/s, {gib(profile.device_memory_bytes)}"
        f" at {profile.mem_bw:.3g} B/s; source: {row['source']}")
    log(f"[device] host mesh {dict(make_host_mesh().shape)}; the allocator "
        f"may hand out {gib(device_bytes(dev, 'bytes_limit'))}")

    failed = []

    def phase(name, fn, *a):
        # a failed phase is reported and the next one still runs, so one
        # run shows every fault; any failure makes the exit code 1
        t = time.perf_counter()
        try:
            return fn(*a)
        except Exception:  # noqa: BLE001 - re-raised as the exit code
            traceback.print_exc()
            failed.append(name)
            return None
        finally:
            gc.collect()
            log(f"[time] {name}: {time.perf_counter() - t:.1f} s "
                f"(compilation included)")

    phase("train", train_phase, get_config(ARCH), profile, dev, args.seed)
    eng = phase("serve", serve_phase, profile, args.seed)
    if eng is None:
        failed.append("kernels (needs the serving cache; not run)")
    else:
        phase("kernels", kernel_phase, eng, args.seed)
    if failed:
        log(f"[failed] {failed}")
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
