"""plan_error (%): how far the planner's spread of time over the conv
layers is from the measured one, as the total variation distance
1/2 * sum_i |m_i / sum m - p_i / sum p|.

m_i is all device time (kernel and glue) in conv layer i's scope in the
traced window; p_i is the plan's ``predicted_s`` for that layer, from the
layer table the program's executor registered (``repro.spans``).  0 when
the plan puts the time where the chip spends it, 100 when no layer
overlaps.  Moves ``images_per_s``: the planner picks algorithms and blocks
by its predictions.
"""
from harness import scopes


def read(ctx):
    sc = scopes.for_run(ctx)
    table = sc.layer_table() if sc is not None else None
    if table is None:
        return None
    predicted = {l["scope"]: l["predicted_s"] for l in table["layers"]
                 if l["kind"] == "conv" and l["predicted_s"] is not None}
    layers = sc.layers()
    measured = {s: (layers[s].total_s if s in layers else 0.0) for s in predicted}
    m, p = sum(measured.values()), sum(predicted.values())
    if m <= 0 or p <= 0:
        return None
    return 50.0 * sum(abs(measured[s] / m - predicted[s] / p) for s in predicted)
