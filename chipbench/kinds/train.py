"""The training driver.

Set-up makes the weights (the configuration's reference module, in the
program's layout, bfloat16, one jitted call from the seed), float32 master
weights and Adam moments, and the token batch (traffic/generator.py).  It
captures the step with a first ``GlobalController``, sets the memory
budget to the mix's share of the captured step's free-at-last-use peak,
and submits the job once, to a second controller under the mix's
pipeline, with swaps on a worker thread or not as the mix's
``async_swap`` says.  That job is the one object the rest of the run drives.

Every iteration gets its own batch (``traffic/generator.py``, by seed and
step), put in place of the job's batch as it enters the executor.  The
first ``check_steps`` iterations are set-up: the first compiles every
equation; after the first and the last of them the driver reads what the
comparison needs (the gradient from the first step's Adam moment, the
master weights' change).  The window opens when they are done and closes
at the end of the first iteration that finishes after ``--seconds``;
the next iteration is refused (``WindowClosed``) and the job ends.  The
controller replans after every iteration whose measured op latencies
drift from those its plan was made with; the driver logs how many
replans came before each iteration.

After the window the device peak is read, the program's state is freed,
and the plain reference reruns the first ``check_steps`` steps in
float32 from the same seed.  Compared, each against a limit in
``cells/<cell>.json``:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: over the leaves, the largest gap between the norms of the
  first gradient, over the larger of the reference's norm of that leaf
  and the median leaf's;
* ``change_gap``: the same for the master weights' change over the
  checked steps, leaving out leaves whose reference gradient is under
  1/1000 of the median leaf's (they move by round-off alone).
"""
from __future__ import annotations

import contextlib
import functools
import gc
import shutil
import tempfile
import threading
import time
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

from chipbench import trace as tracing
from chipbench.traffic import generator

EXCLUDE_BELOW = 1e-3
GIB = 2 ** 30


class WindowClosed(Exception):
    """Ends the training job at the first iteration after the window."""


# ----------------------------------------------------------------------
# the configuration and the reference
# ----------------------------------------------------------------------
def register_config(model: dict):
    """Register the configuration under its name in ``repro.configs`` so
    that the program resolves it like one of its own."""
    from repro.configs import CONFIGS
    from repro.configs.base import LayerSpec, ModelConfig
    kw = dict(model)
    if "block" in kw:
        kw["block"] = tuple(LayerSpec(**b) for b in kw["block"])
    cfg = ModelConfig(**kw)
    CONFIGS[cfg.name] = cfg
    return cfg


def reference_module(cell):
    import importlib
    return importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")


def param_key(seed: int):
    import jax
    return jax.random.PRNGKey(
        int(generator.rng(seed, 0).integers(0, 2 ** 31)))


def make_params(ref, model: dict, seed: int):
    """The weights, bfloat16, on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp
    init = jax.jit(functools.partial(ref.init_params, model,
                                     dtype=jnp.bfloat16))
    return init(param_key(seed))


def batch_of(tokens: np.ndarray):
    import jax.numpy as jnp
    t = jnp.asarray(tokens)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def _norm():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))))


def _diff_norm():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))


def reference_numbers(cell, seed: int, precision: str = "float32",
                      half_batch: bool = False, unchanged: bool = False,
                      log=print) -> dict:
    """The reference's losses, first-gradient norms and master-weight
    change norms over the checked steps, leaf by leaf.  Moments stay on
    the host between steps so that the reference fits beside nothing
    else on the device.  ``unchanged`` plants the fault of a step that
    returns its state unchanged: no update, so the weights, the Adam
    moment the gradient is read from and the change stay at nought."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference.common import adamw_step, make_dot

    ref = reference_module(cell)
    model, mix = cell.model, cell.traffic
    opt = mix["optimizer"]
    t0 = time.perf_counter()

    def data(step):
        tokens = generator.train_tokens(mix, model["vocab_size"], seed, step)
        if half_batch:
            # a planted fault: half of the batch left out, the mean taken
            # over the rest (half the positions of a one-sequence batch)
            b, s = tokens.shape
            tokens = tokens[:b // 2] if b > 1 else tokens[:, :s // 2 + 1]
        return batch_of(tokens)

    p0, tdef = jax.tree.flatten(make_params(ref, model, seed))
    dot = make_dot(precision)

    def loss(leaves, tok, lab):
        return ref.loss(jax.tree.unflatten(tdef, leaves), tok, lab, model,
                        dot)

    grad = jax.jit(jax.value_and_grad(loss))
    upd = jax.jit(functools.partial(
        adamw_step, lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
        eps=opt["eps"]), static_argnums=(4,), donate_argnums=(0, 2, 3))
    norm, diff_norm = _norm(), _diff_norm()
    to32 = jax.jit(lambda x: x.astype(jnp.float32))
    params = [to32(x) for x in p0]
    mu_host: List = [None] * len(params)
    nu_host: List = [None] * len(params)
    losses, gnorms = [], []
    steps = mix["check_steps"]
    for step in range(1, steps + 1):
        batch = data(step)
        value, grads = grad(params, batch["tokens"], batch["labels"])
        losses.append(float(value))
        if step == 1:
            gnorms = [0.0 if unchanged else float(norm(g)) for g in grads]
        if unchanged:
            continue
        for j in range(len(params)):
            if step == 1:
                mu, nu = jnp.zeros_like(params[j]), jnp.zeros_like(params[j])
            else:
                mu, nu = jnp.asarray(mu_host[j]), jnp.asarray(nu_host[j])
            params[j], mu, nu = upd(params[j], grads[j], mu, nu, step)
            grads[j] = None
            if step < steps:
                mu_host[j], nu_host[j] = np.asarray(mu), np.asarray(nu)
            del mu, nu
        del grads
    change = [float(diff_norm(p, q)) for p, q in zip(params, p0)]
    fault = " half batch" * half_batch + " unchanged" * unchanged
    log(f"[reference] {precision}{fault}: "
        f"losses {losses}; {time.perf_counter() - t0:.1f} s")
    return {"losses": losses, "gnorms": gnorms, "change": change,
            "names": leaf_names(p0, tdef)}


def leaf_names(leaves, tdef) -> List[str]:
    import jax
    tree = jax.tree.unflatten(tdef, list(range(len(leaves))))
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _leaf_gap(got, want, keep) -> tuple:
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    floor = float(np.median(want[keep]))
    gaps = np.where(keep, np.abs(got - want) / np.maximum(want, floor), 0.0)
    j = int(np.argmax(gaps))
    return float(gaps[j]), j


def compare(got: dict, want: dict) -> dict:
    """The three numbers compared, each the worst case, and where."""
    lg = [abs(a - b) / abs(b) for a, b in zip(got["losses"], want["losses"])]
    if not all(np.isfinite(got["losses"])):
        lg = [float("inf")]
    g = np.asarray(want["gnorms"])
    every = np.ones(len(g), bool)
    moved = g >= EXCLUDE_BELOW * np.median(g)
    grad_gap, gj = _leaf_gap(got["gnorms"], want["gnorms"], every)
    change_gap, cj = _leaf_gap(got["change"], want["change"], moved)
    names = want["names"]
    return {"loss_gap": (float(max(lg)), f"step {int(np.argmax(lg)) + 1}"),
            "grad_gap": (grad_gap, names[gj]),
            "change_gap": (change_gap, names[cj]),
            "left_out": [n for n, m in zip(names, moved) if not m]}


def judge(found: dict, limits: dict, failed: int = 0, log=print) -> tuple:
    """Each compared number beside its limit, and whether the run is
    correct: no failed step and every number finite and within its
    limit."""
    checks: Dict[str, dict] = {}
    for name in ("loss_gap", "grad_gap", "change_gap"):
        value, where = found[name]
        checks[name] = {"value": value, "limit": limits[name]}
        log(f"[compare] {name} {value!r} at {where}")
    if found["left_out"]:
        log(f"[compare] left out of change_gap: {found['left_out']}")
    correct = failed == 0 and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return checks, correct


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
class Window:
    """Wraps ``JaxprExecutor.run_flat``: counts the job's iterations, feeds
    each its own batch, reads what the comparison needs after the checked
    ones, opens the window after the last of them and ends the job at the
    first iteration that would start after ``seconds``."""

    def __init__(self, seconds: float, n_check: int, on_check, feed,
                 replans, trace_dir=None):
        import jax
        self.jax = jax
        self.seconds = seconds
        self.n_check = n_check
        self.on_check = on_check
        self.feed = feed
        self.replans = replans
        self.trace_dir = trace_dir
        self.iter = 0
        self.t_start = None
        self.t_close = None
        self.ends: List[float] = []
        self.compiles: List[float] = []
        # the controller's replans so far, at the start of each iteration
        self.replans_at: List[int] = []
        self._lock = threading.Lock()

    def count_compile(self, event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            with self._lock:
                self.compiles.append(time.perf_counter())

    def compiles_in_window(self) -> int:
        if self.t_start is None:
            return 0
        end = self.ends[-1] if self.ends else self.t_start
        return sum(self.t_start <= t <= end for t in self.compiles)

    def _open(self) -> None:
        jax = self.jax
        if self.trace_dir is not None:
            jax.profiler.start_trace(
                self.trace_dir, profiler_options=tracing.profiler_options())
        with jax.profiler.TraceAnnotation(tracing.OPEN):
            self.t_start = time.perf_counter()

    def _close(self) -> None:
        jax = self.jax
        with jax.profiler.TraceAnnotation(tracing.CLOSE):
            self.t_close = time.perf_counter()
        if self.trace_dir is not None:
            jax.profiler.stop_trace()

    def wrap(self, orig):
        win = self

        def run_flat(ex, flat):
            i = win.iter + 1
            if (win.t_start is not None
                    and time.perf_counter() - win.t_start >= win.seconds):
                win._close()
                flat.clear()
                ex.close()
                raise WindowClosed()
            win.replans_at.append(win.replans())
            # the step's own batch in place of the job's: every step's
            # rows differ
            batch = win.feed(i)
            flat[len(flat) - len(batch):] = batch
            with win.jax.profiler.TraceAnnotation("chipbench.train_iteration"):
                out = orig(ex, flat)
                win.jax.block_until_ready(out)
            win.iter = i
            if i <= win.n_check:
                win.on_check(i, out)
                if i == win.n_check:
                    win._open()
            else:
                win.ends.append(time.perf_counter())
            return out

        return run_flat


def executor_patches(ex_mod, win: "Window", trace: bool) -> List[tuple]:
    """(owner, attribute, replacement) for the run: the window around
    ``run_flat``; in a traced run also the executor's host work named in
    the trace (each equation's dispatch by its primitive; swaps, fetches
    and recomputes), so that idle gaps can be attributed."""
    import jax
    ann = jax.profiler.TraceAnnotation
    cls = ex_mod.JaxprExecutor
    out = [(cls, "run_flat", win.wrap(cls.run_flat))]
    if not trace:
        return out
    orig_eval = ex_mod._eval_eqn

    def eval_eqn(eqn, invals):
        with ann("eqn:" + eqn.primitive.name):
            return orig_eval(eqn, invals)

    out.append((ex_mod, "_eval_eqn", eval_eqn))
    for meth in ("_swap_out", "_swap_in", "_recompute", "_ensure_input",
                 "_poll_swap_outs"):
        def wrapped(self, *a, _orig=getattr(cls, meth),
                    _name="executor" + meth, **k):
            with ann(_name):
                return _orig(self, *a, **k)

        out.append((cls, meth, wrapped))
    return out


@contextlib.contextmanager
def patched(patches: List[tuple]):
    """Set each (owner, attribute, value) for the block, then restore."""
    saved = [(o, a, getattr(o, a)) for o, a, _ in patches]
    try:
        for o, a, v in patches:
            setattr(o, a, v)
        yield
    finally:
        for o, a, v in reversed(saved):
            setattr(o, a, v)


# ----------------------------------------------------------------------
def device_bytes(dev, key: str) -> int:
    stats = dev.memory_stats()
    return int(stats[key]) if stats and key in stats else 0


def run(cell, *, seed: int, seconds: float, trace: bool, devices, peak,
        log):
    import jax
    from jax import monitoring

    from repro.core import GlobalController, SchedulerConfig
    from repro.core import executor as ex_mod
    from repro.core.cost_model import CostModel, DeviceCalibration
    from repro.core.peak_analysis import analyze
    from repro.core.plan import MachineProfile
    from repro.models.registry import get_model
    from repro.optim import adam
    from repro.service import JobSpec

    dev = devices[0]
    model, mix = cell.model, cell.traffic
    opt = mix["optimizer"]
    cfg = register_config(model)
    api = get_model(cfg)
    ref = reference_module(cell)
    n_check = int(mix["check_steps"])
    b1 = opt["b1"]
    log(f"[train] {cfg.name}: {cfg.param_count() / 1e9:.3f} B parameters; "
        f"batch {mix['batch']} x {mix['seq_len']}; {mix['pipeline']} at "
        f"{mix['budget']['fraction']} x the free-at-last-use peak")

    # -- weights, optimizer state and inputs, from the seed
    params = make_params(ref, model, seed)
    want, _ = api.abstract_params()
    if jax.tree.structure(params) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(params), jax.tree.leaves(want))):
        raise ValueError(f"{cell.config['reference']}.init_params does not "
                         f"make the program's parameter tree for {cfg.name}")
    if opt.get("master_weights") != "float32":
        raise ValueError("the training driver runs float32 master weights")
    opt_state = jax.jit(functools.partial(adam.adamw_init, use_master=True))(
        params)

    def feed(step):
        return jax.tree.leaves(batch_of(generator.train_tokens(
            mix, model["vocab_size"], seed, step)))

    data = batch_of(generator.train_tokens(mix, model["vocab_size"], seed))
    p0_host = [np.asarray(x) for x in jax.tree.leaves(params)]
    n_p = len(p0_host)

    def train_step(params, opt_state, data):
        loss, grads = jax.value_and_grad(lambda p: api.loss(p, data))(params)
        params, opt_state = adam.adamw_update(
            params, grads, opt_state, lr=opt["lr"], b1=opt["b1"],
            b2=opt["b2"], eps=opt["eps"], weight_decay=opt["weight_decay"])
        return params, opt_state, loss

    # -- what the comparison reads from the checked iterations.  Flat
    # outputs: params, then AdamState's step, mu, nu, master, then loss.
    norm, diff_norm = _norm(), _diff_norm()
    prog = {"gnorms": None, "change": None}

    def on_check(i, out):
        if i == 1:
            mu = out[n_p + 1:2 * n_p + 1]
            prog["gnorms"] = [float(norm(m)) / (1.0 - b1) for m in mu]
        if i == n_check:
            master = out[3 * n_p + 1:4 * n_p + 1]
            prog["change"] = [float(diff_norm(m, jax.device_put(p, dev)))
                              for m, p in zip(master, p0_host)]

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    ctl = None
    win = Window(seconds, n_check, on_check, feed,
                 lambda: ctl.replan_count, trace_dir)
    monitoring.register_event_duration_secs_listener(win.count_compile)

    # -- capture, budget, submit
    profile = MachineProfile.for_device(dev) if dev.platform != "cpu" \
        else MachineProfile()
    cost = CostModel(DeviceCalibration(flops=profile.compute_flops,
                                       mem_bw=profile.mem_bw))
    spec = JobSpec(f"train-{cfg.name}", iterations=n_check + 10 ** 6,
                   payload=(train_step, params, opt_state, data))
    del params, opt_state, data
    with jax.profiler.TraceAnnotation("chipbench.capture"):
        captured = GlobalController(
            profile=profile, cost_model=cost,
            pipeline_name=mix["pipeline"]).capture_spec(spec)
    free_peak = analyze([captured.seq]).peak_bytes
    budget = int(mix["budget"]["fraction"] * free_peak)
    ctl = GlobalController(
        profile=profile, cost_model=cost, pipeline_name=mix["pipeline"],
        scheduler_config=SchedulerConfig(memory_budget_bytes=budget),
        async_swap=mix["async_swap"])
    with patched(executor_patches(ex_mod, win, trace)):
        with jax.profiler.TraceAnnotation("chipbench.controller_submit"):
            handle = ctl.submit(spec, captured=captured)
        del spec, captured
        log(f"[train] {len(handle.seq.operators)} equations; free-at-last-"
            f"use peak {free_peak / GIB:.3f} GiB; budget "
            f"{budget / GIB:.3f} GiB; planned peak "
            f"{handle.plan.planned_peak_bytes / GIB:.3f} GiB")
        ctl.wait(raise_errors=False)
    monitoring.unregister_event_duration_listener(win.count_compile)
    err = handle.error
    if not isinstance(err, WindowClosed):
        log(f"[train] the job failed in iteration {win.iter + 1}; "
            f"iterations (s): {[round(t, 3) for t in handle.step_times]}; "
            f"replans before each: {win.replans_at}")
        raise RuntimeError(f"the training job failed before the window "
                           f"closed:\n{handle.error_tb}")
    memory_peak = device_bytes(dev, "peak_bytes_in_use")

    # -- what the window measured
    steps = len(win.ends)
    window_s = win.ends[-1] - win.t_start
    tokens_per_step = mix["batch"] * mix["seq_len"]
    losses = [float(np.asarray(o[0])) for o in handle.outputs]
    stats = handle.stats[n_check:n_check + steps]
    plan = handle.plan
    log(f"[train] set-up iterations (s): "
        f"{[round(t, 3) for t in handle.step_times[:n_check]]}; replans "
        f"before each: {win.replans_at[:n_check + 1]}")
    log(f"[train] window: {steps} steps in {window_s:.3f} s "
        f"({[round(t, 3) for t in handle.step_times[n_check:]]}); "
        f"{win.compiles_in_window()} compilations and "
        f"{win.replans_at[-1] - win.replans_at[n_check]} replans in the "
        f"window; swap-outs {[s.swap_out_count for s in stats]}, "
        f"recomputes {[s.recompute_count for s in stats]}; device peak "
        f"{memory_peak / GIB:.3f} GiB")
    failed = sum(not np.isfinite(x) for x in losses[n_check:])
    layer = SimpleNamespace(
        kind="train", chips=len(devices), window_s=window_s,
        train_tokens_per_s=steps * tokens_per_step / window_s,
        planned_peak_bytes=plan.planned_peak_bytes if plan else 0,
        swap_outs=[s.swap_out_count for s in stats],
        stall_s=[s.stall_time_s for s in stats],
        flops_per_token=ref.train_flops_per_token(model, mix["seq_len"]),
        peak_flops=peak["bf16_flops_per_s"], trace=None)

    # -- free the program's state before the reference runs
    handle.error = handle.error_tb = None
    del err, handle, ctl, stats, plan
    gc.collect()
    log(f"[train] device bytes in use after freeing the job: "
        f"{device_bytes(dev, 'bytes_in_use') / GIB:.3f} GiB")
    if trace_dir is not None:
        layer.trace = tracing.summarize(tracing.load_events(
            tracing.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the comparison
    want = reference_numbers(cell, seed, log=log)
    got = {"losses": losses[:n_check], **prog}
    found = compare(got, want)
    checks, correct = judge(found, cell.limits, failed, log)
    return SimpleNamespace(
        t_window_start=win.t_start, memory_peak_bytes=memory_peak,
        end_to_end={"train_tokens_per_s": layer.train_tokens_per_s,
                    "device_peak_gib": memory_peak / GIB},
        layer=layer, trace=layer.trace, attempted=steps, failed=failed,
        correct=correct, checks=checks,
        compiles_in_window=win.compiles_in_window())
