"""How uneven the routing was over the window: for each MoE layer, the
rows routed to its most-loaded held expert over the mean of its held
experts' rows (the member's device-side counts, read once after the
window); the largest over the layers."""


def read(r):
    layers = r.get("expert_rows_window")
    if not layers:
        return None
    ratios = [max(rows) * len(rows) / sum(rows) for rows in layers
              if sum(rows) > 0]
    return max(ratios) if ratios else None
