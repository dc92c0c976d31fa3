"""``lib/scopes``: device time by named scope out of a hand-made capture
(the protobuf written field by field), and the readers built on it."""
import os

from conftest import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmark.lib import scopes


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(fn, value):
    if isinstance(value, int):
        return _varint(fn << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(fn << 3 | 2) + _varint(len(value)) + value


def _capture(tmp_path, ops, ref_stat=False):
    """One TPU plane whose ``XLA Ops`` line holds ``ops``: (metadata id,
    name, tf_op, offset_ps, duration_ps)."""
    stat_meta = _field(5, _field(1, 7) + _field(2, _field(1, 7) +
                                                _field(2, "tf_op")))
    plane = _field(2, "/device:TPU:0") + stat_meta
    events = b""
    for i, (mid, name, tf_op, off, dur) in enumerate(ops):
        if ref_stat:        # the value interned as a stat-metadata name
            plane += _field(5, _field(1, 100 + i) + _field(
                2, _field(1, 100 + i) + _field(2, tf_op)))
            stat = _field(1, 7) + _field(7, 100 + i)
        else:
            stat = _field(1, 7) + _field(5, tf_op)
        plane += _field(4, _field(1, mid) + _field(
            2, _field(1, mid) + _field(2, name) + _field(5, stat)))
        events += _field(4, _field(1, mid) + _field(2, off) + _field(3, dur))
    plane += _field(3, _field(2, "XLA Ops") + _field(3, 1000) + events)
    plane += _field(3, _field(2, "Steps") + _field(3, 1000)
                    + _field(4, _field(1, 1) + _field(2, 0) + _field(3, 9)))
    host = _field(2, "/host:CPU")
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(_field(1, host) + _field(1, plane))
    return str(tmp_path)


OPS = [(1, "%fusion.1 = f32[8] fusion(...)",
        "jit(mx_lfm2moe_train)/lfm2.conv/mul:", 0, 2_000_000),
       (2, "%fusion.2 = f32[8] fusion(...)",
        "jit(mx_lfm2moe_train)/transpose(jvp(lfm2.moe.experts))/dot:",
        3_000_000, 4_000_000),
       (3, "%ragged-dot-none = bf16[8] custom-call(...)",
        "ragged-dot-none:", 8_000_000, 1_000_000),
       (1, "%fusion.1 = f32[8] fusion(...)",
        "jit(mx_lfm2moe_train)/lfm2.conv/mul:", 10_000_000, 2_000_000)]


def test_read_finds_events_by_scope_on_the_capture_clock(tmp_path):
    got = scopes.read(_capture(tmp_path, OPS),
                      ("lfm2.conv", "lfm2.moe.", "lfm2.attn"))
    # the line starts at 1000 ns; offsets and durations are picoseconds
    assert got["lfm2.conv"] == [(1000, 3000), (11000, 13000)]
    assert got["lfm2.moe."] == [(4000, 8000)]
    assert got["lfm2.attn"] == []


def test_read_follows_an_interned_value(tmp_path):
    got = scopes.read(_capture(tmp_path, OPS, ref_stat=True), ("lfm2.conv",))
    assert got["lfm2.conv"] == [(1000, 3000), (11000, 13000)]


def test_scope_ms_unions_scopes_and_named_kernels_per_step():
    window = ("bench.window", 0, 20000, None)
    step = ("trainer_step", 100, 200, None)
    planes = [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [window, step,
                                        ("trainer_step", 300, 400, None)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("ragged-dot-none", 9000, 10000, "x"),
            ("ragged-dot-metadata", 8900, 9000, "x"),
            ("fusion.2", 4000, 8000, "x")]}]}]
    ctx = {"planes": planes,
           "run": {"scope_events": {"lfm2.moe.": [(4000, 8000)],
                                    "lfm2.conv": [(1000, 3000)]}}}
    # 4000 ns of scope + 1100 ns of kernels, two steps
    assert scopes.scope_ms(ctx, ("lfm2.moe.",), names=("ragged-dot",)) == \
        (4000 + 1100) / 2 / 1e6
    assert scopes.scope_ms(ctx, ("lfm2.conv",)) == 2000 / 2 / 1e6
    assert scopes.scope_ms(ctx, ("lfm2.attn",)) is None
    # a program without the digest (the parent's) reads nothing
    assert scopes.scope_ms({"planes": planes, "run": {}},
                           ("lfm2.conv",)) is None
