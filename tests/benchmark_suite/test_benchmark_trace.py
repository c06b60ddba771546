"""The trace reduction on a synthetic profile: busy union, idle share,
own time by name, program runs, exposed collectives, gap attribution."""

import pytest

from benchmark.lib import trace as T


def _space(planes):
    """planes: {plane: {line: [(name, start_ns, dur_ns, stat_text)]}} ->
    a ProfileData built from an XSpace text proto."""
    from jax.profiler import ProfileData

    out = []
    for pname, lines in planes.items():
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = []
        for lname, evs in lines.items():
            es = []
            for name, start, dur, *stat in evs:
                st = f' stats {{ metadata_id: 1 str_value: "{stat[0]}" }}' \
                    if stat else ""
                es.append(f"events {{ metadata_id: {ids[name]} offset_ps: "
                          f"{int(start * 1000)} duration_ps: "
                          f"{int(dur * 1000)}{st} }}")
            body.append(f'lines {{ name: "{lname}" timestamp_ns: 0 '
                        + " ".join(es) + " }")
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in ids.items())
        out.append(f'planes {{ name: "{pname}" ' + " ".join(body) + " " + meta
                   + ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')
    return ProfileData.from_text_proto("\n".join(out))


@pytest.fixture(scope="module")
def reduced():
    # named as the chip names them: by the whole HLO instruction
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0)", 100, 200),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.1)", 250, 100),
           ("%while.1 = (f32[8]{0}) while(f32[8]{0} %fusion.2)", 500, 300),
           ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p.2)", 520, 100),
           ("%jvp_flash_fwd_.7 = (bf16[4]{0}) custom-call(bf16[4]{0} "
            "%fusion.3)", 650, 100),
           ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %fusion.3)",
            900, 50),
           # an operand's name must not count as this operation's
           ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %jvp_flash_fwd_.7)",
            1200, 100)]
    modules = [("jit__decode_fn(123)", 100, 250),
               ("jit__prefill_fn(9)", 500, 300),
               ("jit__decode_fn(123)", 900, 400)]
    host = [("bench.window", 0, 2000), ("bench.submit", 0, 90),
            ("bench.step", 90, 900), ("bench.generator_sleep", 1000, 180),
            ("bench.step", 1300, 600), ("other", 10, 10)]
    return T.from_profile(_space({
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules,
                          "Steps": [("1", 0, 10)]},
        "/host:CPU": {"main": host}}))


def test_window_and_busy_union(reduced):
    assert reduced.window == (0.0, 2000.0)
    assert T.busy(reduced, 0) == [(100.0, 350.0), (500.0, 800.0),
                                  (900.0, 950.0), (1200.0, 1300.0)]
    assert T.busy_s(reduced) == pytest.approx(700e-9)
    assert T.window_s(reduced) == pytest.approx(2000e-9)


def test_idle_share(reduced):
    assert T.idle_share(reduced) == pytest.approx(1 - 700 / 2000)


def test_own_time_by_name(reduced):
    # the while's own time excludes the two operations nested in it
    assert T.op_seconds(reduced, r"^while") == pytest.approx(100e-9)
    assert T.op_seconds(reduced, "flash_fwd") == pytest.approx(100e-9)
    top = dict(T.top_ops(reduced, 10))
    # one kind for every fusion of that shape; fusion.2 starts inside
    # fusion.1 and the overlap is counted once
    assert top["fusion = f32[8] fusion(f32[8])"] == pytest.approx(450e-9)
    assert top["jvp_flash_fwd_ = (bf16[4]) custom-call(bf16[4])"] == \
        pytest.approx(100e-9)
    assert [e.dur for e in T.op_events(reduced, "flash_fwd")] == [100.0]


def test_program_runs(reduced):
    runs = T.program_runs(reduced, "_decode_fn")
    assert [e.dur for e in runs] == [250.0, 400.0]
    assert [e.dur for e in T.program_runs(reduced, "_prefill_fn")] == [300.0]


def test_exposed_collective(reduced):
    assert T.exposed_collective_s(reduced) == pytest.approx(50e-9)


def test_gap_attribution(reduced):
    gaps = dict(T.idle_gaps(reduced))
    # 0-100: submit covers 90 of it; 350-500, 800-900, 950-1200 (step
    # covers 40, the sleep 180), 1300-2000 under the second step
    assert gaps["submit"] == pytest.approx(100e-9)
    assert gaps["step"] == pytest.approx((150 + 100 + 700) * 1e-9)
    assert gaps["generator_sleep"] == pytest.approx(250e-9)
    assert sum(gaps.values()) == pytest.approx(1300e-9)


def test_host_time_in_a_span(reduced):
    # first step span 90..990: busy inside it 250 + 300 + 50 = 600
    assert T.span_host_ms(reduced, "step") == pytest.approx(
        [300e-6, (600 - 0) * 1e-6])


def test_interval_arithmetic():
    assert T.union([(5, 6), (0, 2), (1, 3), (3, 3)]) == [(0, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.clip([(0, 5), (8, 12)], (4, 10)) == [(4, 5), (8, 10)]
    assert T.total([(0, 3), (5, 6)]) == 4


def test_two_chips_are_averaged():
    tr = T.from_profile(_space({
        "/device:TPU:0": {"XLA Ops": [("%fusion.1 = f32[8]", 0, 100)]},
        "/device:TPU:1": {"XLA Ops": [("%fusion.1 = f32[8]", 0, 50),
                                      ("%all-gather.2 = f32[8]", 50, 30)]},
        "/host:CPU": {"main": [("bench.window", 0, 100)]}}))
    assert T.busy_s(tr) == pytest.approx(90e-9)
    assert T.exposed_collective_s(tr) == pytest.approx(15e-9)
    assert T.idle_share(tr) == pytest.approx(0.1)


# -- a recorded trace -----------------------------------------------------------
# Trimmed from the first traced chip runs of PR 23 (TPU v5 lite, jax 0.9.0,
# libtpu 0.0.34): the device's ``XLA Ops`` / ``XLA Modules`` lines and the
# benchmark's host spans for the first 0.8 s (train) and 0.35 s (chat) of the
# traced window.  The numbers below were read off these files once; they
# guard the reduction, they are not performance records.
def _recorded(tmp_path, cell):
    import gzip
    import os

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", cell + ".xplane.pb.gz")
    dst = tmp_path / "plugins" / "profile" / "run" / "vm.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(src, "rb") as f:
        dst.write_bytes(f.read())
    return T.load(T.newest_xplane(str(tmp_path)))


def test_recorded_train_trace(tmp_path):
    tr = _recorded(tmp_path, "train-gpt1.3b-seq2048")
    assert list(tr.devices) == [0]
    assert T.window_s(tr) == pytest.approx(0.8)
    assert T.busy_s(tr) == pytest.approx(0.784675577, rel=1e-6)
    assert T.idle_share(tr) == pytest.approx(0.0191555, rel=1e-4)
    # the three flash kernels carry their Pallas names; 12 layers, two steps
    flash = T.op_events(tr, "flash_fwd")
    assert len(flash) == 27
    assert {e.name.rstrip(".0123456789") for e in flash} == {"jvp_flash_fwd_"}
    assert T.op_seconds(tr, "flash_(fwd|bwd_dq|bwd_dkv)") == \
        pytest.approx(0.117173373, rel=1e-6)
    kinds = [k for k, _ in T.top_ops(tr, 10)]
    assert any(k.startswith("jvp_flash_fwd_ = ") for k in kinds)
    assert not any("%" in k or "{" in k for k in kinds)
    gaps = dict(T.idle_gaps(tr))
    assert gaps["step"] == pytest.approx(0.01206282, rel=1e-5)
    assert sum(gaps.values()) + T.busy_s(tr) == pytest.approx(0.8)
    assert T.span_host_ms(tr, "step") == pytest.approx([4.681, 4.357],
                                                       abs=1e-3)


def test_recorded_serving_trace(tmp_path):
    tr = _recorded(tmp_path, "serve-mistral7b-chat")
    assert T.window_s(tr) == pytest.approx(0.35)
    assert T.idle_share(tr) == pytest.approx(0.1004966, rel=1e-4)
    decode = T.program_runs(tr, "_decode_fn")
    assert [round(e.dur / 1e6, 3) for e in decode] == [45.521, 45.65, 45.783]
    prefill = T.program_runs(tr, "_prefill_fn")
    assert len(prefill) == 14
    assert sum(e.dur for e in prefill) / 1e6 == pytest.approx(168.671134)
    top = T.top_ops(tr, 3)
    # the page-table gather of every layer is ONE kind, and the largest
    assert top[0][0].startswith("fusion = bf16[1280,128,8,128] fusion("
                                "bf16[900,128,8,128]")
    assert top[0][1] == pytest.approx(0.06034, rel=1e-3)
    assert T.span_host_ms(tr, "step") == pytest.approx(
        [7.926, 11.083, 10.806], abs=1e-3)
    assert T.exposed_collective_s(tr) == 0.0
