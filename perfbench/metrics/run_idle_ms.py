"""run_idle_ms (ms): device-idle time inside the program's ``run`` spans
(``CompiledCNN.run``: the input cast, the executor lookup, the call up to
the jitted dispatch; ``repro.spans``), per executor call in the traced
window, mean over chips.  The share of the host's idle gaps that is the
program's own path.  Moves ``images_per_s``.
"""
from harness import scopes


def read(ctx):
    sc = scopes.for_run(ctx)
    if sc is None or not ctx.window.forwards:
        return None
    if not any(n == "run" for n, _, _ in sc.program_spans):
        return None
    idle = sc.idle_in_spans("run")
    return 1e3 * sum(idle.values()) / len(idle) / ctx.window.forwards
