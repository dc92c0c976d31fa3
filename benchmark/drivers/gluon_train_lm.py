"""Driver: a gluon training job on a language model built from its
configuration (``gluon.model_zoo.text``): fixed-length sequences of token
ids, the labels the ids shifted by one, the mean token cross-entropy over
the vocabulary slice, ``Trainer('adam', multi_precision=True)``.

The step, the window, the fence and the summary are ``gluon_train.Cell``'s;
what is image-shaped there is replaced here: the build, the host pool, and
the comparison (``lib/compare_lm``: Adam's first move does not give the
gradient back, so the first gradient is read from ``Parameter.grad()``).
One "img" of ``train_img_per_s`` is one training sample: one sequence.

After each fence (set-up's, the window's, the traced slice's) the driver
reads the program's counter ``net.expert_tokens``: never inside the
stepped loop. In a traced run it digests the capture's named scopes
before the harness throws the capture away.
"""
import numpy as np

from benchmark.drivers import gluon_train
from benchmark.lib import compare_lm, scopes

CHECK_STEPS = gluon_train.CHECK_STEPS
SCOPES = ("lfm2.conv", "lfm2.attn", "lfm2.moe.route", "lfm2.moe.experts",
          "lfm2.dense_mlp", "lfm2.head")


class Cell(gluon_train.Cell):
    def __init__(self, cfg, workload, seed, multi_precision=None):
        super().__init__(cfg, workload, seed)
        self.seq = self.traffic["seq"]
        self.opt = dict(cfg["optimizer_params"])
        if multi_precision is not None:       # the control's (readings)
            self.opt["multi_precision"] = multi_precision
        self.counter_reads = []

    # -- set-up --------------------------------------------------------
    def setup(self):
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.ndarray import NDArray

        self.mx, self.autograd, self.jax = mx, autograd, jax
        cfg = self.cfg
        np.random.seed(self.seed % 2 ** 32)
        mx.random.seed(self.seed % 2 ** 31)
        self.ctx = mx.tpu() if mx.num_tpus() else mx.cpu()
        net = gluon.model_zoo.get_model(cfg["model"], config=cfg,
                                        held=cfg.get("held"),
                                        dtype=cfg["dtype"])
        spec = self.ref.leaves(cfg)
        leaves = [p for n, p in net.collect_params().items()
                  if not n.endswith("expert_tokens")]
        if len(spec) != len(leaves):
            raise RuntimeError("program has %d leaves, reference %d"
                               % (len(leaves), len(spec)))
        made = self.ref.init_params(self.seed, cfg)
        for (name, shape, _), p in zip(spec, leaves):
            if tuple(p.shape) != tuple(shape):
                raise RuntimeError("leaf %s: program %s %r, reference %r"
                                   % (name, p.name, p.shape, shape))
            # the benchmark's weights stand in for the initializer's
            p.set_data(NDArray(made[name].astype(p.dtype)))
        del made
        net.initialize(ctx=self.ctx)          # the counter's buffer
        net.hybridize()
        self.params = list(net.collect_params().values())
        self.leaves = dict(zip((n for n, _, _ in spec), leaves))
        self.trainable = self.ref.trainable(cfg)
        self.trainer = gluon.Trainer(net.collect_params(),
                                     cfg["optimizer"], dict(self.opt))
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.loss_fn.hybridize()
        self.net = net
        self.selections = self._watch_routers(net, gluon)
        self.x_pool, self.y_pool = self.host_batches()
        first, got = [], {"grads": [], "grad_scale": 1.0 / self.batch}
        for i in range(CHECK_STEPS):
            first.append(self._step())
            got["grads"].append(
                {n: np.asarray(self.leaves[n].grad()._data)
                 for n in self.trainable})
            if i == 0:
                got["selections"] = [p.data().asnumpy()
                                     for p in self.selections]
        got["weights"] = self._weights()
        got["losses"] = [self._fetch(l) for l in first]
        self.got = got
        self._fence()
        self._read_counter()

    def _watch_routers(self, net, gluon):
        """Every expert layer's selections, step by step, in buffers of
        the benchmark's own: a forward hook hands the router's first
        output to the block's aux-state write-back (what BatchNorm's
        running statistics use), so the program the window drives is the
        one that is compared."""
        from mxnet_tpu.gluon.block import defer_aux_update

        k = self.cfg["num_experts_per_tok"]
        probes = []
        for i, layer in enumerate(l for l in net.layers if l.sparse):
            probe = gluon.Parameter("bench_selection%d" % i,
                                    shape=(self.batch * self.seq, k),
                                    dtype="int32", init="zeros",
                                    differentiable=False)
            probe.initialize(ctx=self.ctx)
            layer.ff.router.register_forward_hook(
                lambda block, args, out, probe=probe:
                defer_aux_update(probe, out[0]))
            probes.append(probe)
        return probes

    def host_batches(self):
        """The seeded pool: ids uniform over the vocabulary slice, the
        labels the ids shifted by one (the last label wraps)."""
        rng = np.random.default_rng(self.seed)
        ids = rng.integers(0, self.cfg["vocab_size"],
                           (self.traffic["host_batches"], self.batch,
                            self.seq)).astype(np.int32)
        return ids, np.roll(ids, -1, axis=2)

    def _weights(self):
        """Every trainable leaf as the optimizer holds it: the float32
        master where there is one, else the parameter."""
        import jax.numpy as jnp

        index = {p.name: i for i, p in enumerate(self.params)}
        states = self.trainer._updaters.states
        mixed = self.opt.get("multi_precision")
        out = {}
        for n in self.trainable:
            p = self.leaves[n]
            held = p.data()
            if mixed and held._data.dtype != jnp.float32:
                held = states[index[p.name]][0]
            out[n] = np.asarray(held._data)
        return out

    def _read_counter(self):
        """(steps so far, visits [expert layers, experts] so far)."""
        counts = self.net.expert_tokens.data().asnumpy()
        self.counter_reads.append((self.n_steps, counts.tolist()))

    # -- the measured window ------------------------------------------
    def _run_until(self, deadline):
        end = super()._run_until(deadline)
        self._read_counter()              # after the fence: nothing waits
        return end

    def window(self, seconds, tracer):
        run = super().window(seconds, tracer)
        run["seq"] = self.seq
        # a traced slice is what the second _run_until drove: it lies
        # between the last two readings
        run["counter_reads"] = list(self.counter_reads)
        if tracer:
            run["scope_events"] = scopes.read(tracer.directory, SCOPES)
        return run

    def summary(self, run):
        out = super().summary(run)
        rate = out["end_to_end"]["train_img_per_s"]
        out["extra"] = {"sample": "one sequence of %d tokens" % self.seq,
                        "train_tokens_per_s": rate * self.seq}
        return out

    # -- after the window -----------------------------------------------
    def release(self):
        self.leaves = self.selections = None
        super().release()

    def numbers(self):
        want = self.reference_readings(self.seed, self.cfg, self.x_pool,
                                       self.y_pool)
        return self._compare(self.got, want)

    def _compare(self, got, want):
        """The numbers of lib/compare_lm; the reference's Adam replays
        ``got``'s own gradients from the seed's weights."""
        import jax.numpy as jnp

        ref, names = self.ref, self.ref.trainable(self.cfg)
        params = ref.init_params(self.seed, self.cfg)
        base = {n: np.asarray(params[n]) for n in names}
        adam = ref.make_adam(self.cfg["optimizer_params"])
        m, v = ({n: jnp.zeros(params[n].shape, jnp.float32) for n in names}
                for _ in range(2))
        scale = got.get("grad_scale", 1.0)
        for i, grads in enumerate(got["grads"]):
            g = {n: jnp.asarray(grads[n]).astype(jnp.float32) * scale
                 for n in names}
            params, m, v = adam(params, g, m, v, float(i + 1))
        replay = {n: np.asarray(params[n]) for n in names}
        del params, m, v
        numbers, detail = compare_lm.training_numbers(
            got, want, replay, base, names,
            [n for n in names if ".moe.w" in n])
        self._detail = dict(detail, losses=got["losses"],
                            reference_losses=want["losses"])
        return numbers, self._detail.pop("worst_leaf")

    def reference_readings(self, seed, cfg, x_pool, y_pool, dtype=None,
                           fault=None):
        """The first steps of the plain reference from the same seed and
        batches, its arrays brought to the host. ``dtype`` and ``fault``
        are for the control and the planted faults: a lower precision;
        ``top3`` / ``no_bias`` in the expert layers, or ``half_batch``,
        the second half of every batch left out of the gradient."""
        import jax.numpy as jnp

        ref = self.ref
        rows = self.batch // 2 if fault == "half_batch" else None
        params = ref.init_params(seed, cfg)
        if dtype is not None:
            params = {n: v.astype(dtype) if n in ref.trainable(cfg) else v
                      for n, v in params.items()}
        grad = ref.make_grad(cfg, dtype or jnp.float32,
                             None if rows else fault)
        adam = ref.make_adam(self.cfg["optimizer_params"])
        m, v = ({n: jnp.zeros(params[n].shape, jnp.float32)
                 for n in ref.trainable(cfg)} for _ in range(2))
        out = {"losses": [], "grads": []}
        for i in range(CHECK_STEPS):
            ids = jnp.asarray(x_pool[i % len(x_pool)][:rows])
            labels = jnp.asarray(y_pool[i % len(x_pool)][:rows])
            loss, g, sels = ref.batch_grad(grad, params, ids, labels)
            out["losses"].append(loss)
            out["grads"].append({n: np.asarray(a) for n, a in g.items()})
            if i == 0:
                out["selections"] = [np.asarray(s) for s in sels]
            params, m, v = adam(params, g, m, v, float(i + 1))
            del g
        out["weights"] = {n: np.asarray(params[n])
                          for n in ref.trainable(cfg)}
        return out


def readings(cfg, workload, seeds, what, seconds=0.0):
    """For benchmark/control.py: the numbers of lib/compare_lm over
    several seeds in one process; ``what`` may name several readings with
    commas between, which then share each seed's reference. ``program``:
    the program against the reference (the lower readings). ``control``:
    the same program without ``multi_precision`` (its 16-bit weights
    cannot hold three Adam steps). ``control_ref``: the reference in
    bfloat16 in the program's place. ``top3`` / ``no_bias`` /
    ``half_batch``: the planted faults, in the reference in the
    program's place (``half_batch`` leaves the routing alone: its
    selections are not compared)."""
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
            if one == "half_batch":
                got["selections"] = want["selections"]
            numbers, where = cell._compare(got, want)
            yield dict(seed=seed, what=one, numbers=numbers,
                       worst_leaf=where, **cell.detail())
            del got
