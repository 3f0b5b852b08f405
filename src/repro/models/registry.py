"""Model registry helpers: exact parameter counts from the declarative specs."""

from __future__ import annotations

import numpy as np

from repro.configs.base import ModelConfig
from repro.models import params as P


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact count from specs; ``active_only`` scales MoE experts by k/E."""
    from repro.models import lm

    specs = lm.param_specs(cfg)
    total = P.count_params(specs)
    if not active_only or not cfg.is_moe:
        return total
    # Identify expert weights (w_gate/w_up/w_down with leading E axis).
    expert = 0
    import jax
    flat, _ = jax.tree.flatten_with_path(specs, is_leaf=P.is_spec)
    for path, spec in flat:
        keys = [getattr(p, "key", None) for p in path]
        if "moe" in keys and any(k in ("w_gate", "w_up", "w_down") for k in keys):
            expert += int(np.prod(spec.shape))
    active = total - expert + expert * cfg.experts_per_token // cfg.num_experts
    return active
