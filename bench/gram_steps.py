"""The fused kernel's gram steps, read from the ``session.dispatch`` spans.

Over the fused kernel each ``session.dispatch`` span carries
``gram_steps``, the row-gram iterations the kernel's encode runs for the
call's read lengths (each ``bb``-row batch tile runs its longest read's
grams), and ``gram_steps_shape``, what it would run with every tile at
the padded row's full gram count.  Their ratio over the window is how
much of the padded shape's encode the kernel still runs.  A program
whose spans lack the two arguments gives nothing.
"""

from __future__ import annotations

from bench import program_spans

DISPATCH = "session.dispatch"


def gram_step_pct(prog: program_spans.Program, lo: float, hi: float
                  ) -> float | None:
    """100 x sum ``gram_steps`` / sum ``gram_steps_shape`` over the
    ``session.dispatch`` spans that start in ``[lo, hi)``."""
    spans = [s for s in prog.spans if s.name == DISPATCH
             and lo <= s.start < hi and "gram_steps_shape" in s.args]
    shape = sum(s.args["gram_steps_shape"] for s in spans)
    if shape == 0:
        return None
    return 100.0 * sum(s.args["gram_steps"] for s in spans) / shape


def read(run) -> float | None:
    prog = program_spans.of_run(run)
    return None if prog is None else gram_step_pct(prog, *run.span)
