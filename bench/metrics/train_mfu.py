"""Trainer: model FLOPs utilization of the traced window.  (6 x weights
that multiply each token + the causal attention products, forward and
backward; ``flops.train_flops_per_token``) x tokens trained per second in
the window, over the chips' summed peak bf16 rate.  Recomputation under
remat does not count."""
import flops


def read(r):
    rec = r.records
    if not rec.get("steps"):
        return None
    per_token = flops.train_flops_per_token(r.cfg["model"], r.mix["seq_len"])
    rate = rec["steps"] * rec["tokens_per_step"] / rec["window_s"]
    return 100.0 * per_token * rate / (rec["chips"]
                                       * r.peaks["bf16_flops_per_s"])
