"""What a layer leaves beside its output, and where each value goes.

Two flax collections the Trainer knows (worker/trainer.py): every leaf
sown into AUX_LOSS is added to the training objective; STEP_METRICS
holds the LAST step's scalars and rides in `model_state` to the task's
one fetch (worker/sync.py: fetch_loss).

Which metric a sown scalar feeds is declared beside its `sow`, by the
module that sows it:

    step_metrics.declare(
        "ssm_state_kept_ratio",
        metrics_lib.default_registry().gauge(
            "worker_ssm_state_kept_ratio", "...", labelnames=("layer",)
        ),
    )
    ...
    sow_step_metric(self, "ssm_state_kept_ratio", value)

and both train loops hand what they fetched to `publish`, which knows no
name: a declared leaf sets its gauge (or adds to its counter) under the
module's path, anything else is the caller's to write as a summary
scalar.
"""

from __future__ import annotations

from types import MappingProxyType
from typing import Dict, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.common import metrics as metrics_lib

AUX_LOSS = "aux_loss"
STEP_METRICS = "step_metrics"

# sown leaf's name -> the metric family of the default registry it feeds
_METRICS: Dict[str, object] = {}


def sow_step_metric(module: nn.Module, name: str, value) -> None:
    """Keep `value` (the LAST step's, not a history) in STEP_METRICS.
    One value a PATH: a module applied several times a step (a block
    inside a looped model's trips) sows under one path each time and keeps
    its LAST application's value.  What has to come out a trip leaves the
    loop with a trip axis and is sown under a path a trip
    (`model_zoo/ouro/ouro.py: TripGauges`)."""
    value = jax.lax.stop_gradient(jnp.asarray(value, jnp.float32))
    module.sow(
        STEP_METRICS, name, value,
        reduce_fn=lambda previous, new: new,
        init_fn=lambda: jnp.zeros(value.shape, jnp.float32),
    )


def declare(leaf: str, metric):
    """Every value sown under the name `leaf` feeds `metric`, a family of
    the default registry: a gauge is set, a counter added to, under the
    sowing module's path where the family has a label (`layer`,
    `table`).  Returns `metric`.  One name feeds one metric: a second
    declaration with another raises where it is made."""
    known = _METRICS.setdefault(leaf, metric)
    if known is not metric:
        raise ValueError(
            f"step metric {leaf!r} already feeds {known.name}; it cannot "
            f"also feed {metric.name}"
        )
    return metric


def declared() -> Mapping[str, object]:
    """A read-only view of the table: sown leaf's name -> metric."""
    return MappingProxyType(_METRICS)


def publish(sown: Mapping[str, float]) -> Dict[str, float]:
    """Feed a task's fetched `{path: value}` (path = module path / leaf)
    to the declared metrics; return the values no declaration claims."""
    rest = {}
    for path, value in sown.items():
        prefix, _, leaf = path.rpartition("/")
        metric = _METRICS.get(leaf)
        if metric is None:
            rest[path] = value
            continue
        series = metric
        if metric.labelnames:
            series = metric.labels(**{metric.labelnames[0]: prefix})
        if metric.kind == metrics_lib.COUNTER:
            series.inc(value)
        else:
            series.set(value)
    return rest
