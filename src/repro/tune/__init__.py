"""repro.tune — the closed tune->execute loop (paper §5 applied to the code).

``core.autotune`` picks knobs from the analytic/calibrated memory model;
this package turns those knobs into cached :class:`KernelPlan`s that the
Pallas kernels (:mod:`repro.kernels.ops`) and model attention call sites
(:mod:`repro.models.attention`) consume as their *defaults* — so measured
knob choices actually reach the datapath instead of stopping at a report.

Quick use::

    from repro.tune import plan_for
    plan = plan_for("flash_attention", shape_sig=(4096, 4096, 128))
    plan.bq, plan.bkv, plan.pipeline_depth, plan.resolve_interpret()
"""
from repro.tune.cache import (PlanCache, default_cache,  # noqa: F401
                              plan_for, set_default_cache)
from repro.tune.plan import (KERNELS, KernelPlan, auto_interpret,  # noqa: F401
                             derive_attention_plan, derive_decode_plan,
                             derive_matmul_plan, derive_paged_plan,
                             derive_plan, plan_key, spec_fingerprint)

__all__ = [
    "KernelPlan", "KERNELS", "auto_interpret", "plan_key", "spec_fingerprint",
    "derive_plan", "derive_attention_plan", "derive_decode_plan",
    "derive_matmul_plan", "derive_paged_plan",
    "PlanCache", "default_cache", "set_default_cache",
    "plan_for",
]
