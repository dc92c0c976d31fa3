"""Driver: ``gluon_train_lm``'s training job on the SmallThinker decoder
(``gluon.model_zoo.text.smallthinker``, reference
``benchmark/reference/smallthinker.py``). The build, the step, the window
and the comparison are ``gluon_train_lm``'s: the model comes from the
configuration's ``model`` key, and a planted fault is a word the reference
understands (``no_window``: the window layers see every earlier key;
``rope_all``: the full layers rotated too; ``router_after``: the router
reads the experts' input; ``silu_experts``; ``top5``: one expert fewer per
token; ``half_batch``). What differs: the digest of a traced run (this
model's named scopes beside the routed experts', which keep
``lfm2.moe.*``), and ``half_batch``, which at a batch of one sequence is
the reference's own fault (the second half of the sequence's tokens left
out of the loss) and not a slice of rows.
"""
import numpy as np

from benchmark.drivers import gluon_train, gluon_train_lm
from benchmark.lib import scopes

CHECK_STEPS = gluon_train_lm.CHECK_STEPS
SCOPES = ("smallthinker.attn.window", "smallthinker.attn.full",
          "smallthinker.head", "lfm2.moe.route", "lfm2.moe.experts")


class Cell(gluon_train_lm.Cell):
    def window(self, seconds, tracer):
        # gluon_train_lm's window with this model's scopes: the capture
        # is parsed once
        run = gluon_train.Cell.window(self, seconds, tracer)
        run["seq"] = self.seq
        run["counter_reads"] = list(self.counter_reads)
        if tracer:
            run["scope_events"] = scopes.read(tracer.directory, SCOPES)
        return run

    def reference_readings(self, seed, cfg, x_pool, y_pool, dtype=None,
                           fault=None):
        """``gluon_train_lm``'s, every fault handed to the reference as
        it is: no batch is sliced."""
        import jax.numpy as jnp

        ref = self.ref
        names = ref.trainable(cfg)
        params = ref.init_params(seed, cfg)
        if dtype is not None:
            params = {n: v.astype(dtype) if n in names else v
                      for n, v in params.items()}
        grad = ref.make_grad(cfg, dtype or jnp.float32, fault)
        adam = ref.make_adam(self.cfg["optimizer_params"])
        m, v = ({n: jnp.zeros(params[n].shape, jnp.float32) for n in names}
                for _ in range(2))
        out = {"losses": [], "grads": []}
        for i in range(CHECK_STEPS):
            ids = jnp.asarray(x_pool[i % len(x_pool)])
            labels = jnp.asarray(y_pool[i % len(x_pool)])
            loss, g, sels = ref.batch_grad(grad, params, ids, labels)
            out["losses"].append(loss)
            out["grads"].append({n: np.asarray(a) for n, a in g.items()})
            if i == 0:
                out["selections"] = [np.asarray(s) for s in sels]
            params, m, v = adam(params, g, m, v, float(i + 1))
            del g
        out["weights"] = {n: np.asarray(params[n]) for n in names}
        return out


def readings(cfg, workload, seeds, what, seconds=0.0):
    """For benchmark/control.py, as ``gluon_train_lm.readings``, over this
    driver's cell: ``program``; ``control`` (no ``multi_precision``);
    ``control_ref`` (the reference in bfloat16 in the program's place);
    the planted faults, in the reference in the program's place."""
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
            del got
