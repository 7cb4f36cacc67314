"""Pieces every driver shares."""

from __future__ import annotations

import numpy as np

#: engine counters that mean a plan degraded or an executor failed.
BAD_COUNTER_PREFIXES = ("errors:", "breaker_")
BAD_COUNTERS = ("embed_fallbacks", "prefilter_degraded", "quarantined_graphs",
                "train_skipped_steps", "nonfinite_targets")


def program_config(config: dict):
    """The program's SimGNN configuration for a benchmark configuration."""
    from repro.core.simgnn import SimGNNConfig

    return SimGNNConfig(n_node_labels=config["n_node_labels"],
                        gcn_dims=tuple(config["gcn_dims"]),
                        ntn_k=config["ntn_k"],
                        fcn_dims=tuple(config["fcn_dims"]),
                        max_nodes=config["max_nodes"], dtype=config["dtype"])


def fresh(graph: dict) -> dict:
    """A new request dict for a pooled graph: the program memoizes hashes on
    the dict, and real traffic arrives as new objects."""
    return {"adj": graph["adj"], "labels": graph["labels"]}


def bad_counters(counters) -> dict:
    return {k: int(v) for k, v in counters.items()
            if v and (k.startswith(BAD_COUNTER_PREFIXES) or k in BAD_COUNTERS)}


def new_records(recorder, before: int) -> list:
    """Trace records appended since the recorder held `before`."""
    n = recorder.total_records - before
    return recorder.records()[-n:] if n > 0 else []


def quantiles(values) -> dict:
    v = np.asarray(values, np.float64)
    if not v.size:
        return {}
    return {"p50": float(np.percentile(v, 50)),
            "p95": float(np.percentile(v, 95)), "max": float(v.max())}
