"""Model FLOP/s utilization of the whole serving step where prefill does
the work: target model FLOPs of the prompts admitted in the window plus
those of the tokens delivered in it, over the window, as a share of the
chip's peak bf16 FLOP/s."""
from onchip import counts


def read(run):
    if not run.peak:
        return None
    flops = counts.delivered_flops(run.target, [
        (c, k) for _, t, k, c in run.rec.deliveries if run.in_window(t)])
    flops += sum(counts.prefill(run.target, n - 1).flops
                 for t, n in run.rec.admissions if run.in_window(t))
    return 100.0 * flops / run.window_s / float(run.peak["bf16_flops_per_s"]) or None
