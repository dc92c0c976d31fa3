"""Driver: ``gluon_train_laguna``'s training job on the DeepSeek-V3 decoder
(``gluon.model_zoo.text.deepseek_v3``, reference
``benchmark/reference/deepseek_v3.py``) built from Moonlight-16B-A3B's
configuration. The build, the step, the window, the comparison (the
replayed and the first weights brought to the host a leaf at a time) and
the batch of one sequence are ``gluon_train_laguna``'s; every fault is
handed to the reference as it is (``no_latent_norm``,
``rotate_half_pairs``, ``scale_128``, ``bias_in_weights``,
``softmax_router``, ``no_routed_scale``, ``one_shared_expert``). What
differs is the digest of a traced run: this model's named scopes beside
the routed experts', which keep ``lfm2.moe.*``. The routers'
``e_score_correction_bias`` buffers are leaves of the reference's that
no optimizer moves: the build loads them with the weights, and the
comparison reads the trainable leaves alone.
"""
import gc

from benchmark.drivers import gluon_train, gluon_train_laguna

CHECK_STEPS = gluon_train_laguna.CHECK_STEPS
SCOPES = ("moonlight.attn.mla", "moonlight.dense_mlp",
          "moonlight.shared_expert", "moonlight.head", "lfm2.moe.route",
          "lfm2.moe.experts")


class Cell(gluon_train_laguna.Cell):
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


def readings(cfg, workload, seeds, what, seconds=0.0):
    """For benchmark/control.py, as ``gluon_train_laguna.readings`` over
    this driver's cell: ``program``; ``control`` (no ``multi_precision``);
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
            del got, cell
            gc.collect()
