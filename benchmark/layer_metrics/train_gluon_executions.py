"""Executions of gluon's programs (``jit_mx_<block>_<mode>``) per step of
the traced slice: 3 while the forward runs twice, plain from
``block.call`` and again inside ``jax.vjp``, before the transposed
program."""
from benchmark.lib import spans


def read(ctx):
    return spans.modules_per_step(ctx["planes"], "jit_mx_")
