"""Share of the roofline reached by admission prefill
(``chunk_prefill_paged``, both models): the least time of prefilling the
prompts admitted in the window, each in one pass (``counts.prefill``), over
the device time of the prefill programs in the trace."""
from onchip import counts


def read(run):
    if run.trace is None or not run.peak:
        return None
    dev_s, calls = run.trace.module_time("chunk_prefill_paged")
    if not calls:
        return None
    least = sum(counts.prefill(m, n - 1).least_s(run.peak)[0]
                for t, n in run.rec.admissions if run.in_window(t)
                for m in (run.target, run.draft))
    return 100.0 * least / dev_s if least else None
