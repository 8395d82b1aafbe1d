"""Percent of its roofline that the MoE layers' grouped expert product
reached in the traced window: the least time the window's grouped
products could take (each the larger of its FLOPs over the chip's bf16
peak and its bytes over the HBM bandwidth, from the routed-row counts
and the published widths; ``kinds/train_seq.gmm_ideal_s``) over their
device time in the trace (the ``ragged-dot`` kernels)."""


def read(r):
    g = r.get("expert_gmm")
    if not g or not g.get("device_s") or not r.get("steps"):
        return None
    from chipbench.kinds.train_seq import gmm_ideal_s
    ideal = gmm_ideal_s(r["expert_rows_window"], r["steps"], g["d"], g["f"],
                        g["held"], r["peaks"])
    return 100.0 * ideal / g["device_s"]
