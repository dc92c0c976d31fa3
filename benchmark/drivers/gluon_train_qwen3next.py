"""Driver: ``gluon_train_lm``'s training job on the Qwen3-Next decoder
(``gluon.model_zoo.text.qwen3_next``, reference
``benchmark/reference/qwen3_next.py``). The build, the step, the window,
the comparison and the readings are ``gluon_train_lm``'s: the model comes
from the configuration's ``model`` key, and a planted fault is a word the
reference understands (``top9``: one expert fewer per token; ``no_decay``:
the rule's ``g = 0``; ``no_shared``: the shared expert left out;
``half_batch``). What differs is the digest of a traced run: this
model's named scopes beside the routed experts', which keep
``lfm2.moe.*``.
"""
from benchmark.drivers import gluon_train, gluon_train_lm
from benchmark.lib import scopes

CHECK_STEPS = gluon_train_lm.CHECK_STEPS
SCOPES = ("qwen3next.gdn.proj", "qwen3next.gdn.conv", "qwen3next.gdn.rule",
          "qwen3next.gdn.out", "qwen3next.attn", "qwen3next.shared_expert",
          "qwen3next.head", "lfm2.moe.route", "lfm2.moe.experts")
readings = gluon_train_lm.readings


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
