"""Share of the chips' peak (the bf16 figure: the step computes in
float32, for which the chip publishes none) that the operations every
POBP implementation must do reach over the window: the dense sweep over
every real token and topic, and one update per power coordinate per
selective iteration (`bench.counting.pobp_step_flops`)."""

from bench.counting import pobp_step_flops


def read(run):
    c = run.counters
    if not c.get("steps") or not c.get("window_s"):
        return None
    flops = sum(pobp_step_flops(nnz, c["num_topics"],
                                min(c["power_words"], words),
                                c["power_topics"], max(iters - 1, 0))
                for nnz, words, iters in zip(c["nnz_per_step"], c["words"],
                                             c["iters"]))
    peak = run.peaks["flops_per_s"] * c["chips"]
    return 100.0 * flops / c["window_s"] / peak
