"""The readers of the program's own spans (``lib/program_spans.py``,
``readers/idle_under_span.py``, ``readers/span_ms.py``) on synthetic profiles
and on one recorded trace per kind of cell."""

import gzip
import os
import types

import pytest

from benchmark.lib import program_spans as PS
from benchmark.lib import registry
from benchmark.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = {
    "sched.idle_admit.serve": ["serve.shed_scan", "serve.admit"],
    "sched.idle_decode_prep.serve": ["serve.decode.prep",
                                     "serve.decode.dispatch"],
    "sched.idle_logits_to_host.serve": ["serve.decode.to_host"],
    "sched.idle_sample.serve": ["serve.decode.sample"],
    "sched.idle_deliver.serve": ["serve.deliver"],
    "sched.idle_prefill_host.serve": [
        "serve.prefill", "serve.prefill.dispatch", "serve.prefill.to_host",
        "serve.prefill.sample"],
    "sched.idle_other.serve": [],
}
SPAN_METRICS = {"train.host_ms_per_step.train": "train.step",
                "guard.host_ms_per_step.train": "train.guard"}


def _space(planes):
    """planes: {plane: {line: [(name, start_ns, dur_ns, {stat: int})]}} -> a
    ProfileData built from an XSpace text proto."""
    from jax.profiler import ProfileData

    out = []
    for pname, lines in planes.items():
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        stats = sorted({k for evs in lines.values() for e in evs
                        for k in (e[3] if len(e) > 3 else {})})
        sids = {n: i + 1 for i, n in enumerate(stats)}
        body = []
        for lname, evs in lines.items():
            es = []
            for name, start, dur, *facts in evs:
                st = "".join(f" stats {{ metadata_id: {sids[k]} "
                             f"int64_value: {v} }}"
                             for k, v in (facts[0] if facts else {}).items())
                es.append(f"events {{ metadata_id: {ids[name]} offset_ps: "
                          f"{int(start * 1000)} duration_ps: "
                          f"{int(dur * 1000)}{st} }}")
            body.append(f'lines {{ name: "{lname}" timestamp_ns: 0 '
                        + " ".join(es) + " }")
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in ids.items())
        smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{n}" }} }}' for n, i in sids.items())
        out.append(f'planes {{ name: "{pname}" ' + " ".join(body) + " "
                   + meta + " " + smeta + " }")
    return ProfileData.from_text_proto("\n".join(out))


def _fusion(i):
    return f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p.{i})"


@pytest.fixture(scope="module")
def profile():
    # busy: 100-300, 400-450, 700-900, 1500-1800   window 0-2000
    ops = [(_fusion(1), 100, 200), (_fusion(2), 400, 50),
           (_fusion(3), 700, 200), (_fusion(4), 1500, 300)]
    host = [("bench.window", 0, 2000), ("bench.step", 40, 1160),
            ("other", 10, 10),
            ("serve.step", 50, 1150, {"step": 7, "active": 2}),
            ("serve.shed_scan", 60, 10), ("serve.admit", 70, 20),
            ("serve.decode", 300, 700, {"rows": 2}),
            ("serve.decode.prep", 310, 50),
            # a gap (450-700) that straddles dispatch and to_host
            ("serve.decode.dispatch", 380, 120),
            ("serve.decode.to_host", 500, 420, {"bytes": 1024}),
            ("serve.decode.sample", 920, 70),
            ("serve.deliver", 1000, 150, {"requests": 2, "tokens": 2}),
            ("serve.step", 1300, 100, {"step": 8, "active": 2}),
            ("train.step", 1900, 60, {"step": 1})]
    other_thread = [("serve.compile", 1950, 30)]
    return _space({
        "/device:TPU:0": {"XLA Ops": ops,
                          "XLA Modules": [("jit_serve_decode_fn(1)", 100, 200)]},
        "/host:CPU": {"main": host, "worker": other_thread}})


@pytest.fixture(scope="module")
def reduced(profile):
    return T.from_profile(profile)


@pytest.fixture(scope="module")
def spans(profile):
    return PS.from_profile(profile)


def test_only_the_programs_spans_are_kept_with_their_facts(spans):
    assert [s.name for s in spans][:4] == [
        "serve.step", "serve.shed_scan", "serve.admit", "serve.decode"]
    assert {s.name.split(".")[0] for s in spans} == {"serve", "train"}
    assert len(spans) == 12                 # both threads' lines
    assert dict(spans[0].facts) == {"step": 7, "active": 2}
    assert (spans[0].start, spans[0].end, spans[0].dur) == (50.0, 1200.0,
                                                            1150.0)
    byname = {s.name: s for s in spans}
    assert dict(byname["serve.decode.to_host"].facts) == {"bytes": 1024}


def test_owner_is_the_deepest_covering_span(spans):
    pieces = PS.owners(spans)
    at = lambda t: next(n for a, b, n in pieces if a <= t < b)    # noqa: E731
    assert at(55) == "serve.step" and at(65) == "serve.shed_scan"
    assert at(305) == "serve.decode" and at(320) == "serve.decode.prep"
    assert at(600) == "serve.decode.to_host"
    assert at(1160) == "serve.step"         # after deliver, before its end
    assert at(1960) == "serve.compile"      # another thread's, started last
    assert all(a < b for a, b, _ in pieces)
    assert all(x[1] <= y[0] for x, y in zip(pieces, pieces[1:]))


def test_owner_among_listed_spans_only(spans):
    pieces = PS.owners(s for s in spans if s.name in
                       ("serve.decode.to_host", "serve.deliver"))
    assert pieces == [(500.0, 920.0, "serve.decode.to_host"),
                      (1000.0, 1150.0, "serve.deliver")]


def test_idle_is_attributed_and_a_straddling_gap_is_split(reduced, spans):
    listed = [n for names in NEW_METRICS.values() for n in names]
    idle = PS.idle_by_span(reduced, spans, listed)
    # gaps: 0-100, 300-400, 450-700, 900-1500, 1800-2000
    assert idle["serve.shed_scan"] == 10 and idle["serve.admit"] == 20
    assert idle["serve.decode.prep"] == 50          # 310-360
    assert idle["serve.decode.dispatch"] == 20 + 50  # 380-400, 450-500
    assert idle["serve.decode.to_host"] == 200 + 20  # 500-700, 900-920
    assert idle["serve.decode.sample"] == 70
    assert idle["serve.deliver"] == 150
    # the rest: before the step, the step's and the decode's own time, the
    # runner between steps, the unlisted spans
    assert idle[PS.REST] == 1250 - 590
    assert sum(idle.values()) == pytest.approx(
        T.idle_share(reduced) * 2000)


def test_a_parent_span_listed_takes_what_its_children_leave(reduced, spans):
    idle = PS.idle_by_span(reduced, spans, ["serve.decode",
                                            "serve.decode.to_host"])
    # 300-400 and 450-500 lie in serve.decode outside to_host; 920-1000 too
    assert idle["serve.decode"] == 100 + 50 + 80
    assert idle["serve.decode.to_host"] == 220


@pytest.fixture
def ctx(reduced, spans, monkeypatch):
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    return types.SimpleNamespace(trace=reduced)


def _read(reg, name, ctx):
    spec = reg.layer_metric(name)
    return reg.module("readers", spec["reader"]).read(ctx, **spec["args"])


def test_the_seven_shares_partition_the_idle_share(ctx):
    reg = registry.Registry()
    values = {name: _read(reg, name, ctx) for name in NEW_METRICS}
    assert values["sched.idle_admit.serve"] == pytest.approx(100 * 30 / 2000)
    assert values["sched.idle_decode_prep.serve"] == \
        pytest.approx(100 * 120 / 2000)
    assert values["sched.idle_logits_to_host.serve"] == \
        pytest.approx(100 * 220 / 2000)
    assert values["sched.idle_prefill_host.serve"] == 0.0
    assert values["sched.idle_other.serve"] == pytest.approx(100 * 660 / 2000)
    idle_share = reg.module("readers", "idle_share").read(ctx)
    assert sum(values.values()) == pytest.approx(idle_share)


def test_span_ms_is_the_median_length_inside_the_window(ctx, reduced, spans):
    reg = registry.Registry()
    assert PS.lengths_ms(reduced, spans, "serve.step") == \
        pytest.approx([1150e-6, 100e-6])
    assert _read(reg, "train.host_ms_per_step.train", ctx) == \
        pytest.approx(60e-6)
    assert _read(reg, "guard.host_ms_per_step.train", ctx) is None
    narrow = T.Trace(reduced.devices, reduced.spans, (0.0, 1000.0))
    assert PS.lengths_ms(narrow, spans, "serve.step") == []


def test_a_program_without_spans_reads_nothing(reduced, monkeypatch):
    """The parent commit under this PR's benchmark files: no metric, no
    raise."""
    monkeypatch.setattr(PS, "of_run", lambda root=None: [])
    reg = registry.Registry()
    ctx = types.SimpleNamespace(trace=reduced)
    for name in list(NEW_METRICS) + list(SPAN_METRICS):
        assert _read(reg, name, ctx) is None, name


@pytest.mark.parametrize("name", sorted(NEW_METRICS) + sorted(SPAN_METRICS))
def test_metric_files_are_named_and_listed_as_the_issue_says(name):
    reg = registry.Registry()
    spec = reg.layer_metric(name)
    entry = reg._entry("per_layer", name)
    assert spec["source"] == entry["source"] == "program_span"
    if name in NEW_METRICS:
        assert (spec["reader"], spec["unit"]) == ("idle_under_span", "%")
        assert spec["args"] == {"spans": NEW_METRICS[name]}
        # at least the two Mistral cells; every cell whose engine carries
        # the spans may be listed (granite's and deepseek's since PR 33)
        assert {"serve-mistral7b-chat",
                "serve-mistral7b-docqa"} <= set(entry["workloads"])
        assert all(w.startswith("serve-") for w in entry["workloads"])
        assert set(NEW_METRICS[name]) <= set(PS.listed_spans(reg))
    else:
        assert (spec["reader"], spec["unit"]) == ("span_ms", "ms")
        assert spec["args"] == {"span": SPAN_METRICS[name]}
        assert entry["workloads"] == ["train-gpt1.3b-seq2048"]


def test_listed_spans_are_disjoint_lists():
    listed = PS.listed_spans()
    assert sorted(listed) == sorted(n for names in NEW_METRICS.values()
                                    for n in names)
    assert len(listed) == len(set(listed))


def _unpack(tmp_path, cell, fixture):
    dst = tmp_path / ".bench_out" / "trace" / cell / "plugins" / "profile" \
        / "run" / "vm.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "fixtures", fixture), "rb") as f:
        dst.write_bytes(f.read())
    return dst


def test_the_runs_trace_is_the_newest_under_bench_out(tmp_path):
    assert PS.newest_xplane(str(tmp_path)) is None
    assert PS.of_run(str(tmp_path)) == []
    old = _unpack(tmp_path, "cell-a", "train-gpt1.3b-seq2048.xplane.pb.gz")
    new = _unpack(tmp_path, "cell-b", "serve-mistral7b-chat.xplane.pb.gz")
    os.utime(old, (1, 1))
    assert PS.newest_xplane(str(tmp_path)) == str(new)
    # PR 23's recordings: a program that had no spans of its own
    assert PS.of_run(str(tmp_path)) == []


# -- recorded traces --------------------------------------------------------
# Trimmed from this PR's own traced chip runs (PR 24; TPU v5 lite, jax 0.9.0,
# libtpu 0.0.34): the device's ``XLA Ops`` / ``XLA Modules`` lines and the
# host plane's ``bench.*`` / ``serve.*`` / ``train.*`` events of 0.7 s (chat)
# and 1.2 s (train) of the traced window.  The numbers below were read off
# these files once; they guard the reduction, they are not performance
# records.
def _recorded(tmp_path, cell):
    path = _unpack(tmp_path, cell, cell + ".spans.xplane.pb.gz")
    assert PS.newest_xplane(str(tmp_path)) == str(path)
    return T.load(str(path)), PS.of_run(str(tmp_path))


def test_recorded_serving_spans(tmp_path, monkeypatch):
    tr, spans = _recorded(tmp_path, "serve-mistral7b-chat")
    assert T.window_s(tr) == pytest.approx(0.7)
    # the programs under their own names, found by the accepted patterns
    names = {e.name.split("(")[0] for e in tr.devices[0]["modules"]}
    assert {"jit_serve_decode_fn", "jit_serve_prefill_fn"} <= names
    assert len(T.program_runs(tr, "_decode_fn")) == 10
    assert len(T.program_runs(tr, "_prefill_fn")) == 9
    count = {}
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    assert count["serve.step"] == count["serve.decode"] == 12
    assert count["serve.prefill"] == count["serve.prefill.to_host"] == 2
    prefill = next(s for s in spans if s.name == "serve.prefill")
    assert dict(prefill.facts) == {
        "rid": 185, "trace": "9bdca2d9b1655f06", "prompt_tokens": 811,
        "chunks": 7, "cached_tokens": 0}
    to_host = [s for s in spans if s.name == "serve.decode.to_host"]
    assert {dict(s.facts)["bytes"] for s in to_host} == {64 * 32768 * 2}

    idle = PS.idle_by_span(tr, spans, PS.listed_spans())
    assert idle["serve.decode.to_host"] / 1e6 == pytest.approx(47.415874)
    assert idle["serve.decode.sample"] / 1e6 == pytest.approx(40.976279)
    assert idle["serve.prefill.to_host"] / 1e6 == pytest.approx(5.469737)
    assert idle[PS.REST] / 1e6 == pytest.approx(1.689699)
    assert sum(idle.values()) / 1e9 == pytest.approx(
        T.idle_share(tr) * 0.7, rel=1e-9)

    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    reg = registry.Registry()
    ctx = types.SimpleNamespace(trace=tr)
    values = {name: _read(reg, name, ctx) for name in NEW_METRICS}
    assert values["sched.idle_logits_to_host.serve"] == \
        pytest.approx(6.773696, rel=1e-5)
    assert values["sched.idle_sample.serve"] == pytest.approx(5.853754,
                                                              rel=1e-5)
    assert values["sched.idle_other.serve"] == pytest.approx(0.241386,
                                                             rel=1e-5)
    assert sum(values.values()) == pytest.approx(15.589156, rel=1e-6)
    assert sum(values.values()) == pytest.approx(
        reg.module("readers", "idle_share").read(ctx))


def test_recorded_training_spans(tmp_path, monkeypatch):
    tr, spans = _recorded(tmp_path, "train-gpt1.3b-seq2048")
    assert T.window_s(tr) == pytest.approx(1.2)
    names = {e.name.split("(")[0] for e in tr.devices[0]["modules"]}
    assert "jit_train_step_guarded" in names and "jit__unknown" not in names
    assert [s.name for s in spans[:5]] == [
        "train.step", "train.marshal", "train.launch", "train.rebind",
        "train.guard"]
    assert {dict(s.facts).get("program") for s in spans
            if s.name == "train.launch"} == {"train_step_guarded"}
    assert PS.lengths_ms(tr, spans, "train.step") == pytest.approx(
        [6.17613, 6.28273, 6.466509])
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    reg = registry.Registry()
    ctx = types.SimpleNamespace(trace=tr)
    assert _read(reg, "train.host_ms_per_step.train", ctx) == \
        pytest.approx(6.28273)
    assert _read(reg, "guard.host_ms_per_step.train", ctx) == \
        pytest.approx(0.69783)
    # no serving span, so the serving shares read the whole idle as the rest
    idle = PS.idle_by_span(tr, spans, PS.listed_spans())
    assert idle == {PS.REST: pytest.approx(20478937.0)}
