"""Driver: ``gluon_train_lm``'s training job on the Laguna decoder
(``gluon.model_zoo.text.laguna``, reference ``benchmark/reference/laguna.py``).
The build, the step, the window and the comparison are
``gluon_train_lm``'s, and what ``gluon_train_smallthinker`` changes of them
for a batch of one sequence is kept: every fault is handed to the
reference as it is (``no_window``, ``no_yarn``, ``no_head_gate``,
``sigmoid_router``, ``no_routed_scale``, ``top9``, ``half_batch``: the
second half of the sequence's tokens left out of the loss). What differs
is the digest of a traced run: this model's named scopes beside the
routed experts', which keep ``lfm2.moe.*``; and the host's memory: at
811 M parameters one reading's three float32 gradients and weights are
13 GB, so the comparison brings the replayed and the first weights over
a leaf at a time, and the readings let each go before the next.
"""
import gc

import numpy as np

from benchmark.drivers import gluon_train, gluon_train_smallthinker
from benchmark.lib import compare_lm

CHECK_STEPS = gluon_train_smallthinker.CHECK_STEPS
SCOPES = ("laguna.attn.window", "laguna.attn.full", "laguna.dense_mlp",
          "laguna.shared_expert", "laguna.head", "lfm2.moe.route",
          "lfm2.moe.experts")


class Cell(gluon_train_smallthinker.Cell):
    def window(self, seconds, tracer):
        # gluon_train_lm's window with this model's scopes: the capture
        # is parsed once
        from benchmark.lib import scopes

        run = gluon_train.Cell.window(self, seconds, tracer)
        run["seq"] = self.seq
        run["counter_reads"] = list(self.counter_reads)
        if tracer:
            run["scope_events"] = scopes.read(tracer.directory, SCOPES)
        return run

    def _compare(self, got, want):
        """``gluon_train_lm``'s numbers, the reference's Adam replaying
        ``got``'s own gradients from the seed's weights; the replayed and
        the first weights stay on the device and each leaf comes to the
        host when it is read."""
        import jax.numpy as jnp

        ref, names = self.ref, self.ref.trainable(self.cfg)
        params = ref.init_params(self.seed, self.cfg)
        adam = ref.make_adam(self.cfg["optimizer_params"])
        m, v = ({n: jnp.zeros(params[n].shape, jnp.float32) for n in names}
                for _ in range(2))
        scale = got.get("grad_scale", 1.0)
        for i, grads in enumerate(got["grads"]):
            g = {n: jnp.asarray(grads[n]).astype(jnp.float32) * scale
                 for n in names}
            params, m, v = adam(params, g, m, v, float(i + 1))
            del g
        del m, v
        numbers, detail = compare_lm.training_numbers(
            got, want, _OnHost(params),
            _OnHost(ref.init_params(self.seed, self.cfg)), names,
            [n for n in names if ".moe.w" in n])
        self._detail = dict(detail, losses=got["losses"],
                            reference_losses=want["losses"])
        return numbers, self._detail.pop("worst_leaf")


class _OnHost:
    """{leaf: device array} read as {leaf: host array}, a leaf at a time
    and nothing kept: the copy read is a fresh array's, so the host value
    a device array keeps once read stays off the leaf that lives on."""

    def __init__(self, arrays):
        self._arrays = arrays

    def __getitem__(self, name):
        import jax.numpy as jnp

        return np.asarray(jnp.copy(self._arrays[name]))


def readings(cfg, workload, seeds, what, seconds=0.0):
    """For benchmark/control.py, as ``gluon_train_smallthinker.readings``
    (whose loop names its own cell), over this driver's cell:
    ``program``; ``control`` (no ``multi_precision``); ``control_ref``
    (the reference in bfloat16 in the program's place); the planted
    faults, in the reference in the program's place."""
    import jax.numpy as jnp

    for seed in seeds:
        want = None
        for one in what.split(","):
            cell = Cell(cfg, workload, seed,
                        multi_precision=False if one == "control" else None)
            if one in ("program", "control"):
                cell.setup()
                got = cell.got
                pools = cell.x_pool, cell.y_pool
                cell.release()
            else:
                pools = cell.host_batches()
                kw = {"dtype": jnp.bfloat16} if one == "control_ref" \
                    else {"fault": one}
                got = cell.reference_readings(seed, cfg, *pools, **kw)
            if want is None:
                want = cell.reference_readings(seed, cfg, *pools)
            numbers, where = cell._compare(got, want)
            yield dict(seed=seed, what=one, numbers=numbers,
                       worst_leaf=where, **cell.detail())
            del got, cell
            gc.collect()
