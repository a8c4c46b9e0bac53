"""Row-gram iterations the fused kernel's encode ran, over what the
padded shapes would run, from the ``session.dispatch`` spans' arguments."""

from bench.gram_steps import read  # noqa: F401
