"""``residual_share`` read from a synthetic scoped trace."""
from types import SimpleNamespace

import pytest

from harness import scopes
from harness.spec import metric_reader

O = scopes.ScopedOp


def _scoped(residual: bool) -> scopes.Scoped:
    pool, conv, route, proj, add = ("L001.maxpool", "L004.conv.direct",
                                    "L005.route", "L006.conv.direct",
                                    "L007.shortcut")
    ops = {"/device:TPU:0": [
        O("reduce-window.1 reduce-window f32[8,56,56,64]", 0, 30, False,
          f"jit(fwd_r)/{pool}/reduce_window_max:", pool),
        O("pad.4 pad f32[25600,128]", 30, 35, False,
          f"jit(fwd_r)/{conv}/pad:", conv),
        O("L004.conv.direct.1 pallas f32[25600,128]", 35, 135, True,
          f"jit(fwd_r)/{conv}/pallas_call:", conv),
        # Rows back to B*H*W: a crop of the kernel's row padding.
        O("slice.1 slice f32[25088,128]", 135, 139, False,
          f"jit(fwd_r)/{conv}/slice:", conv),
        # Channels back to the logical 64: the crop the reference forces.
        O("slice.2 slice f32[8,56,56,64]", 139, 149, False,
          f"jit(fwd_r)/{conv}/slice:", conv),
        O("copy.3 copy f32[8,56,56,64]", 149, 154, False,
          f"jit(fwd_r)/{route}/copy:", route),
        O("pad.6 pad f32[8,56,56,128]", 154, 164, False,
          f"jit(fwd_r)/{proj}/pad:", proj),
        O("L006.conv.direct.1 pallas f32[25600,256]", 164, 264, True,
          f"jit(fwd_r)/{proj}/pallas_call:", proj),
        O("add_maximum_fusion fusion f32[8,56,56,64]", 264, 284, False,
          f"jit(fwd_r)/{add}/max:", add)]}
    marks = {pool: "source", conv: "source", route: "branch", proj: None,
             add: "add"}
    layers = []
    for scope, mark in marks.items():
        index, kind, _ = scopes.parse_scope(scope)
        row = {"scope": scope, "index": index, "kind": kind,
               "predicted_s": None}
        if residual:
            row["residual"] = mark
        layers.append(row)
    table = {"name": "fwd_r", "layers": layers}
    return scopes.Scoped(ops, [("window", 0, 1000)], [], [table])


@pytest.mark.parametrize("residual", [True, False], ids=["marked", "parent"])
def test_residual_share(residual, monkeypatch):
    sc = _scoped(residual)
    monkeypatch.setattr(scopes, "for_run", lambda ctx: sc)
    ctx = SimpleNamespace(reduced=SimpleNamespace(total_busy_s=284e-9),
                          window=SimpleNamespace(forwards=1))
    got = metric_reader("residual_share")(ctx)
    if not residual:
        # A layer table without residual marks (the parent's) reads nothing.
        assert got is None
        return
    # The channel crop (10) in a source scope, the route's copy (5) and the
    # add (20); not the pool, the source's pad, kernel or row crop, nor the
    # consumer's pad and kernel.
    assert got == pytest.approx(100 * (10 + 5 + 20) / 284)
