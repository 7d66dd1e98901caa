"""Device-resident observability plane (see ``repro.obs.state``)."""
from repro.obs.cost import (CostModel, TierCost, boundary_io_us,
                            compaction_io_us, drain_io_us, step_io_us)
from repro.obs.export import (bucket_bounds, bucket_of_us_np, events_table,
                              hist_delta, hist_sum_delta,
                              quantile_from_hist, quantiles_from_hist,
                              snapshot, timeline_table)
from repro.obs.profile import maybe_trace
from repro.obs.state import (EV_COMMIT, EV_RESUME, EV_START,
                             EVENT_KIND_NAMES, KIND_NAMES, N_KINDS,
                             STEP_COMP_BUCKETS, TICK,
                             TRIG_POLICY, TRIG_RATE_LIMIT, TRIG_WATERMARK,
                             TRIGGER_NAMES, ObsConfig, ObsState,
                             bucket_of_us, counter_delta, init,
                             record_compaction, record_drain, record_step)


def __getattr__(name: str):
    # TIMELINE_FIELDS needs repro.core (Counters._fields); resolving it
    # lazily keeps `import repro.obs` from importing repro.core while
    # repro.core.engine is itself mid-import of this package
    if name == "TIMELINE_FIELDS":
        from repro.obs.state import TIMELINE_FIELDS
        return TIMELINE_FIELDS
    raise AttributeError(name)

__all__ = [
    "CostModel", "TierCost", "boundary_io_us", "compaction_io_us",
    "drain_io_us", "step_io_us",
    "bucket_bounds", "bucket_of_us_np", "events_table", "hist_delta",
    "hist_sum_delta", "quantile_from_hist", "quantiles_from_hist",
    "snapshot", "timeline_table",
    "maybe_trace", "EV_COMMIT", "EV_RESUME", "EV_START",
    "EVENT_KIND_NAMES", "KIND_NAMES", "N_KINDS", "STEP_COMP_BUCKETS", "TICK",
    "TIMELINE_FIELDS", "TRIG_POLICY", "TRIG_RATE_LIMIT", "TRIG_WATERMARK",
    "TRIGGER_NAMES", "ObsConfig", "ObsState", "bucket_of_us",
    "counter_delta", "init", "record_compaction", "record_drain",
    "record_step",
]
