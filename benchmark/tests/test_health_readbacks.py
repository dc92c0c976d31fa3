"""``train_health_readbacks`` on a hand-made host plane: reads inside
and outside ``trainer.health``, on two threads, and a capture without
the span."""
import importlib.util
import os

import pytest

from benchmark.lib import xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader():
    spec = importlib.util.spec_from_file_location(
        "readbacks_reader", os.path.join(BENCH, "layer_metrics",
                                         "train_health_readbacks.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _planes(health_spans=True):
    read = "np.asarray(jax.Array)"
    main = [(xplane.WINDOW_SPAN, 0, 1000, None)]
    for t in (100, 500):
        main += [("trainer_step", t, t + 300, None),
                 ("trainer.update", t + 10, t + 100, None),
                 (read, t + 20, t + 30, None),        # the update's own
                 ("PjitFunction(fn)", t + 120, t + 140, None)]
        if health_spans:
            main += [("trainer.health", t + 110, t + 150, None),
                     ("trainer.health", t + 200, t + 290, None),
                     (read, t + 210, t + 220, None),
                     (read, t + 230, t + 240, None),
                     (read, t + 250, t + 260, None)]
        main.append((read, t + 295, t + 299, None))   # after the boundary
    # the loss fetch between steps, and a read that runs past the window
    main += [(read, 420, 440, None), (read, 990, 1010, None)]
    # another thread reads while the first is inside trainer.health
    other = [(read, 305, 315, None), (read, 710, 720, None)]
    return [{"name": "/host:CPU",
             "lines": [{"name": "python", "events": main},
                       {"name": "feeder", "events": other}]},
            {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops",
                        "events": [("fusion.1", 0, 900, "jit_fn")]}]}]


@pytest.mark.parametrize("health_spans,want", [(True, 3.0), (False, None)])
def test_reads_inside_the_health_spans_per_step(health_spans, want):
    assert _reader().read({"planes": _planes(health_spans)}) == want


def test_a_capture_without_steps_says_nothing():
    planes = _planes()
    events = planes[0]["lines"][0]["events"]
    planes[0]["lines"][0]["events"] = [
        ev for ev in events if ev[0] != "trainer_step"]
    assert _reader().read({"planes": planes}) is None
