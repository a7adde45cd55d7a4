"""99th percentile of the inter-token time, the same samples as the
end-to-end ``itl_mean_ms``.  In the open-loop prefill cells about a
hundredth of the deliveries follow an admission's monolithic prefill, so
this percentile reads that stall; it lands on one admission's stall or the
next, and swings too far from run to run to carry a bound."""
from onchip.harness import itl_samples, percentile


def read(run):
    v = percentile(itl_samples(run.rec, run.t0, run.t1), 99)
    return None if v is None else 1e3 * v
