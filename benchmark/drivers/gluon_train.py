"""Driver: a gluon training job. ``autograd.record`` -> ``loss.backward``
-> ``Trainer.step`` on a pool of seeded host batches, as
``train_imagenet.py`` does it: the loss is fetched every
``disp_batches`` steps and one fence on all parameters ends the window.

Set-up builds the one net and trainer, loads the benchmark's weights
into them, and drives them through the first three steps by the
window's own ``_step``; those steps compile, and they are the steps the
reference follows after the window.
"""
import gc
import importlib
import time

import numpy as np

CHECK_STEPS = 3


def _now():
    return time.monotonic_ns()


class Cell:
    def __init__(self, cfg, workload, seed):
        self.cfg, self.wl, self.seed = cfg, workload, int(seed)
        self.traffic = workload["traffic_params"]
        self.batch = self.traffic["batch"]
        self.ref = importlib.import_module(
            "benchmark.reference." + cfg["reference"])
        self.spans = {"feed": [], "fwd_bwd": [], "update": []}
        self.step_ends = []
        self.n_steps = 0

    # -- set-up --------------------------------------------------------
    def setup(self):
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.ndarray import NDArray

        self.mx, self.autograd, self.jax = mx, autograd, jax
        cfg, hw = self.cfg, self.cfg["image_size"]
        np.random.seed(self.seed % 2 ** 32)
        mx.random.seed(self.seed % 2 ** 31)
        self.ctx = mx.tpu() if mx.num_tpus() else mx.cpu()
        net = gluon.model_zoo.vision.get_model(cfg["model"],
                                               classes=cfg["classes"])
        net.initialize(mx.init.Xavier(), ctx=self.ctx)
        net.hybridize()
        # the usual shape-resolving forward (PERF.md, PR 21): a block
        # with deferred shapes runs its first call op by op
        net(mx.nd.zeros((2, cfg["in_channels"], hw, hw), ctx=self.ctx))
        self.params = list(net.collect_params().values())
        spec = self.ref.leaves(cfg)
        made = self.ref.init_params(self.seed, cfg)
        if len(spec) != len(self.params):
            raise RuntimeError("program has %d leaves, reference %d"
                               % (len(self.params), len(spec)))
        for (name, shape, _), p in zip(spec, self.params):
            if tuple(p.shape) != tuple(shape):
                raise RuntimeError("leaf %s: program %s %r, reference %r"
                                   % (name, p.name, p.shape, shape))
            p.set_data(NDArray(made[name]))
        del made
        opt = dict(cfg["optimizer_params"])
        self.trainer = gluon.Trainer(net.collect_params(),
                                     cfg["optimizer"], opt)
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.net = net
        self.x_pool, self.y_pool = self.host_batches()
        self.snap = [self._leaves()]
        first = []
        for i in range(CHECK_STEPS):
            first.append(self._step())
            if i in (0, CHECK_STEPS - 1):
                self.snap.append(self._leaves())
        self.first_losses = [self._fetch(l) for l in first]
        self._fence()

    def host_batches(self):
        """The seeded pool of host batches: images N(0, 1), labels
        uniform over the classes; every row differs."""
        cfg, hw = self.cfg, self.cfg["image_size"]
        rng = np.random.default_rng(self.seed)
        pool = self.traffic["host_batches"]
        return (rng.standard_normal(
            (pool, self.batch, cfg["in_channels"], hw, hw),
            dtype=np.float32),
            rng.integers(0, cfg["classes"], (pool, self.batch)))

    def _leaves(self):
        return [p.data()._data for p in self.params]

    def _fetch(self, loss):
        return float(loss.mean().asscalar())

    def _fence(self):
        for p in self.params:
            p.data().wait_to_read()

    def _step(self):
        """One training step, as the window drives it."""
        mx, prof = self.mx, self.jax.profiler
        i = self.n_steps % len(self.x_pool)
        t0 = _now()
        with prof.TraceAnnotation("bench.feed"):
            x = mx.nd.array(self.x_pool[i], ctx=self.ctx)
            y = mx.nd.array(self.y_pool[i].astype(np.float32), ctx=self.ctx)
        t1 = _now()
        with prof.TraceAnnotation("bench.fwd_bwd"):
            with self.autograd.record():
                loss = self.loss_fn(self.net(x), y)
            loss.backward()
        t2 = _now()
        with prof.TraceAnnotation("bench.update"):
            self.trainer.step(self.batch)
        t3 = _now()
        self.spans["feed"].append((t0, t1))
        self.spans["fwd_bwd"].append((t1, t2))
        self.spans["update"].append((t2, t3))
        self.step_ends.append(t3)
        self.n_steps += 1
        return loss

    # -- the measured window ------------------------------------------
    def _run_until(self, deadline):
        disp = self.traffic["disp_batches"]
        while _now() < deadline:
            loss = self._step()
            if self.n_steps % disp == 0:
                with self.jax.profiler.TraceAnnotation("bench.loss_fetch"):
                    self.last_loss = self._fetch(loss)
        with self.jax.profiler.TraceAnnotation("bench.fence"):
            self._fence()
        return _now()

    def window(self, seconds, tracer):
        """Runs ``seconds``; in a traced run the last
        ``trace_seconds`` of them are the traced slice and the rates
        come from the part before it."""
        slice_s = self.wl["trace_seconds"] if tracer else 0.0
        w0 = _now()
        w1 = self._run_until(w0 + int((seconds - slice_s) * 1e9))
        if tracer:
            with tracer:
                self._run_until(_now() + int(slice_s * 1e9))
        return {"w0_ns": w0, "w1_ns": w1, "batch": self.batch,
                "step_ends_ns": list(self.step_ends),
                "spans_ns": self.spans}

    def summary(self, run):
        from benchmark.lib import stats

        acc = stats.train_window(run["step_ends_ns"], run["batch"],
                                 run["w0_ns"], run["w1_ns"])
        return {"attempted": acc["steps"], "failed": 0,
                "end_to_end": {"train_img_per_s": acc["train_img_per_s"]}}

    # -- after the window -----------------------------------------------
    def release(self):
        self.net = self.trainer = self.loss_fn = self.params = None
        gc.collect()

    def numbers(self):
        """The reference follows the first three steps; see
        lib/compare.training_numbers for what is compared."""
        from benchmark.lib import compare

        got = self.program_readings()
        want = self.reference_readings(self.seed, self.cfg, self.x_pool,
                                       self.y_pool)
        names = [n for n, _, _ in self.ref.leaves(self.cfg)]
        self._detail = {"losses": got["losses"],
                        "reference_losses": want["losses"]}
        return compare.training_numbers(got, want,
                                        self.ref.trainable(self.cfg), names)

    def detail(self):
        return self._detail

    def program_readings(self):
        opt = self.cfg["optimizer_params"]
        names = [n for n, _, _ in self.ref.leaves(self.cfg)]
        grad, move = self.ref.leaf_norms(
            self.snap[0], self.snap[1], self.snap[2], names,
            opt["learning_rate"], opt["wd"])
        self.snap = None
        return {"losses": self.first_losses, "grad": grad, "move": move}

    def reference_readings(self, seed, cfg, x_pool, y_pool, dtype=None,
                           rows=None):
        """Three steps of the plain reference from the same seed and
        batches. ``dtype`` and ``rows`` are for the control and the
        planted fault (benchmark/control.py): a lower precision, or only
        the first ``rows`` rows of each batch."""
        import jax.numpy as jnp

        ref = self.ref
        opt = cfg["optimizer_params"]
        names = [n for n, _, _ in ref.leaves(cfg)]
        params = ref.init_params(seed, cfg)
        step = ref.make_step(cfg, opt, dtype or jnp.float32)
        if dtype is not None:
            params = {n: v.astype(dtype) for n, v in params.items()}
        mom = ref.zero_momentum(params, cfg)
        snaps, losses = [params], []
        for i in range(CHECK_STEPS):
            x = x_pool[i % len(x_pool)][:rows]
            y = y_pool[i % len(x_pool)][:rows]
            loss, params, mom = step(params, mom, jnp.asarray(x),
                                     jnp.asarray(y, jnp.int32))
            losses.append(float(loss))
            if i in (0, CHECK_STEPS - 1):
                snaps.append(params)
        grad, move = ref.leaf_norms(
            *[[s[n] for n in names] for s in snaps], names,
            opt["learning_rate"], opt["wd"])
        return {"losses": losses, "grad": grad, "move": move}


def readings(cfg, workload, seeds, what, seconds=0.0):
    """For benchmark/control.py: the numbers of lib/compare over several
    seeds in one process. ``program``: the program against the
    reference (the lower readings; no measured window is needed).
    ``control``: the reference in bfloat16 put in the program's place.
    ``half_batch``: the planted fault, the reference with half of every
    batch left out and the mean taken over the rest."""
    import jax.numpy as jnp

    from benchmark.lib import compare

    for seed in seeds:
        cell = Cell(cfg, workload, seed)
        if what == "program":
            cell.setup()
            got = cell.program_readings()
            pools = cell.x_pool, cell.y_pool
            cell.release()
        else:
            pools = cell.host_batches()
            if what == "control":
                got = cell.reference_readings(seed, cfg, *pools,
                                              dtype=jnp.bfloat16)
            elif what == "half_batch":
                got = cell.reference_readings(seed, cfg, *pools,
                                              rows=cell.batch // 2)
            else:
                raise ValueError(what)
        want = cell.reference_readings(seed, cfg, *pools)
        names = [n for n, _, _ in cell.ref.leaves(cfg)]
        numbers, where = compare.training_numbers(
            got, want, cell.ref.trainable(cfg), names)
        yield {"seed": seed, "what": what, "numbers": numbers,
               "worst_leaf": where, "losses": got["losses"],
               "reference_losses": want["losses"]}
