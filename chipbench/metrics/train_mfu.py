"""Model FLOP/s utilization of the whole split-NN step: the model FLOPs
of one sample (three times the forward of every bottom and the top, as
the configuration's reference counts them; the member's recomputed
forward is not counted) times samples per second, over the chip's bf16
peak, in percent. float32 products count against the bf16 peak, the
higher of the chip's two, so the share can only be understated."""


def read(r):
    rate = r["samples"] / r["window_s"]
    return 100.0 * r["flops_per_sample"] * rate / (
        r["peaks"]["bf16_flops_per_s"] * r["device"]["count"])
