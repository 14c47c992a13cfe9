"""TENSILE plan → compiled-path JAX artifacts (the production integration).

The interpreter path executes plans event-by-event; at pod scale the same
decisions must be *baked into* the compiled step function instead:

  * recompute decisions  → a `jax.checkpoint` policy over
    `checkpoint_name`-tagged activations (XLA rematerializes them in the
    backward pass — the compiled equivalent of a Recompute event);
  * swap decisions on activations → offloaded saveables
    (`save_and_offload_only_these_names`) where the backend supports memory
    spaces;
  * Opt-phase across-iteration swaps → optimizer-state / master-weight
    pytree leaves placed in `pinned_host` shardings between steps (the
    paper's Fig. 1(c), as residency rather than as events).

CPU caveat (documented in DESIGN.md §2): XLA's CPU SPMD partitioner rejects
`annotate_device_placement`, so on this container `backend_supports_memory_kinds()`
is False and offload decisions degrade to accounting (reported bytes move to
the host ledger; the dry-run compiles without the annotations).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, FrozenSet, Optional

import jax
from jax import ad_checkpoint

from .access import AccessSequence, TensorKind
from .plan import MachineProfile, SchedulingPlan
from .scheduler import MemoryScheduler, SchedulerConfig


@functools.lru_cache(maxsize=4)
def backend_supports_memory_kinds(platform: Optional[str] = None) -> bool:
    """Probe: can this backend compile a host-offload annotation under SPMD?
    A backend that cannot be asked raises; it is never read as "no"."""
    dev = jax.devices(platform)[0] if platform else jax.devices()[0]
    if dev.platform == "cpu":
        # the CPU SPMD partitioner rejects annotate_device_placement
        # (verified empirically; see DESIGN.md §2)
        return False
    return any(m.kind == "pinned_host" for m in dev.addressable_memories())


@dataclasses.dataclass(frozen=True)
class TensileDecisions:
    """Distilled plan for the compiled path."""
    remat_names: FrozenSet[str] = frozenset()     # recompute these activations
    offload_names: FrozenSet[str] = frozenset()   # host-offload these
    save_names: FrozenSet[str] = frozenset()      # keep these resident
    offload_opt_state: bool = False               # Opt-phase across-iteration
    offload_master: bool = False
    device_peak_estimate: int = 0
    host_bytes_estimate: int = 0

    def summary(self) -> str:
        return (f"remat={sorted(self.remat_names)} "
                f"offload={sorted(self.offload_names)} "
                f"opt_host={self.offload_opt_state} "
                f"master_host={self.offload_master}")


def make_remat_policy(decisions: TensileDecisions, offload: bool = False):
    """Checkpoint policy implementing the plan's keep/recompute/offload split.

    Tag activations in the model with `jax.ad_checkpoint.checkpoint_name`;
    names in `save_names` stay resident, names in `offload_names` go to host
    (TPU) or stay resident (CPU fallback), everything else rematerializes.
    """
    save = set(decisions.save_names)
    off = set(decisions.offload_names)
    if offload and backend_supports_memory_kinds():
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=sorted(save),
            names_which_can_be_offloaded=sorted(off),
            offload_src="device", offload_dst="pinned_host")
    return jax.checkpoint_policies.save_only_these_names(
        *sorted(save | off))


def opt_state_sharding(base_sharding, *, host: bool):
    """Place an optimizer-state leaf on host when supported (the Opt-phase
    across-iteration swap of paper Fig. 1(c) as a residency decision)."""
    if not host or not backend_supports_memory_kinds():
        return base_sharding
    return base_sharding.with_memory_kind("pinned_host")


# ----------------------------------------------------------------------
def plan_decisions(seq: AccessSequence, plan: SchedulingPlan,
                   name_of_tensor: Optional[Dict[str, str]] = None,
                   ) -> TensileDecisions:
    """Summarize a planned schedule into compiled-path decisions.

    `name_of_tensor` maps captured tensor ids to checkpoint_name tags; when
    absent, decisions are expressed per tensor-kind (opt-state/master
    offload + biggest-activation names by shape signature).
    """
    remat, offload = set(), set()
    opt_host = False
    host_bytes = 0
    for ev in plan.events:
        spec = seq.tensors.get(ev.tensor_id)
        if spec is None:
            continue
        from .plan import EventType
        if ev.event_type is EventType.RECOMPUTE:
            tag = (name_of_tensor or {}).get(ev.tensor_id,
                                             _shape_tag(spec))
            remat.add(tag)
        elif ev.event_type is EventType.SWAP_OUT:
            if spec.kind in (TensorKind.OPT_STATE,) or spec.updates:
                opt_host = True
                host_bytes += spec.size_bytes
            else:
                tag = (name_of_tensor or {}).get(ev.tensor_id,
                                                 _shape_tag(spec))
                offload.add(tag)
                host_bytes += spec.size_bytes
    return TensileDecisions(
        remat_names=frozenset(remat), offload_names=frozenset(offload),
        offload_opt_state=opt_host,
        device_peak_estimate=plan.planned_peak_bytes,
        host_bytes_estimate=host_bytes)


def _shape_tag(spec) -> str:
    return f"{spec.kind.value}:{'x'.join(map(str, spec.shape))}:{spec.dtype}"


# ----------------------------------------------------------------------
def schedule_for_budget(seq: AccessSequence, budget_bytes: int,
                        profile: Optional[MachineProfile] = None,
                        ) -> TensileDecisions:
    """One-call entry: plan a captured step under a device-memory budget and
    return the compiled-path decisions."""
    sched = MemoryScheduler(
        profile or MachineProfile(),
        SchedulerConfig(memory_budget_bytes=budget_bytes))
    sched.register_job(seq)
    res = sched.schedule()
    return plan_decisions(seq, res.plans[seq.job_id])


def checkpoint_name(x, name: str):
    """Re-export for model code (tag activations for policy decisions)."""
    return ad_checkpoint.checkpoint_name(x, name)
