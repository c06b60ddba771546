"""``readers/cycle_account.py`` on a hand-built span list: what the slow
tenth of the gaps was made of, from the engine's account on ``serve.deliver``.

Two populations, computed by hand below: 90 plain cycles of 15 ns that close
4 gaps each, and 10 cycles of 100 ns that stood behind an 85 ns prefill of
512 tokens, close 4 gaps each and hand the prefilled request its index 0 and
1 at once (a gap of zero).  410 gaps; the band is the ranks [369, 405.9)."""

import types

import pytest

from benchmark.lib import clock, registry
from benchmark.lib import program_spans as PS
from benchmark.lib import trace as T

CELLS = ["serve-mistral7b-chat", "serve-mistral7b-docqa",
         "serve-granite4hmicro-chat-bursty",
         "serve-deepseekv3-ep16-reasoning",
         "serve-nemotron3nano-ep8-reasoning"]
LAYER = "serving scheduler (serving.ServingEngine host loop)"
METRICS = {        # name -> (unit, better, the reader's ``what``)
    "sched.gap_tail_mean.serve": ("ms", "lower", "tail_mean"),
    "sched.gap_tail_prefill.serve": ("%", "lower", "prefill"),
    "sched.gap_tail_decode.serve": ("%", "higher", "decode"),
    "sched.gap_tail_outside.serve": ("%", "lower", "outside"),
    "sched.prefill_tokens_ahead_tail.serve": ("count", "lower",
                                              "prefill_tokens"),
}
# a cycle, ns: the last step's tail, the caller between two steps, admit,
# [prefill], decode, deliver
TAIL, OUTSIDE, ADMIT, PREFILL, DECODE, DELIVER = 1, 1, 1, 85, 10, 2
PLAIN = TAIL + OUTSIDE + ADMIT + DECODE + DELIVER               # 15
BEHIND = PLAIN + PREFILL                                        # 100


def _timeline(account=True):
    """100 cycles after a first flush that only marks their start; every
    tenth (5, 15, ...) stands behind a prefill.  Returns the spans and the
    end of every delivering flush."""
    spans, ends, t, seq = [], [], 0.0, 0

    def step(prefill, facts):
        nonlocal t
        start = t
        t += ADMIT
        if prefill:
            spans.append(PS.Span("serve.prefill", t, t + PREFILL,
                                 (("prompt_tokens", 600),)))
            # children do not count twice
            spans.append(PS.Span("serve.prefill.dispatch", t + 1, t + 40))
            t += PREFILL
        spans.append(PS.Span("serve.decode", t, t + DECODE, (("rows", 4),)))
        t += DECODE
        spans.append(PS.Span("serve.deliver", t, t + DELIVER,
                             tuple(facts.items())))
        t += DELIVER
        ends.append(t)
        spans.append(PS.Span("serve.step", start, t + TAIL))
        t += TAIL + OUTSIDE

    def facts(prefill):
        nonlocal seq
        seq += 1
        f = {"requests": 5, "tokens": 6} if prefill else \
            {"requests": 4, "tokens": 4}
        if account:
            f.update(seq=seq, gaps=4, gaps_long=0,
                     first_tokens=int(prefill), compiled=0,
                     prefill_requests=int(prefill),
                     prefill_tokens=512 if prefill else 0,
                     prefill_launches=int(prefill), decode_rows=4)
        return f

    t = OUTSIDE
    step(False, facts(False))
    for k in range(100):
        step(k % 10 == 5, facts(k % 10 == 5))
    return sorted(spans, key=lambda s: (s.start, -s.end)), ends


def _ctx(spans, window, monkeypatch):
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    return types.SimpleNamespace(trace=T.Trace({}, [], window))


def _read_all(ctx):
    reg = registry.Registry()
    out = {}
    for name in METRICS:
        spec = reg.layer_metric(name)
        out[name] = reg.module("readers", spec["reader"]).read(
            ctx, **spec["args"])
    return out


def test_the_band_of_two_populations_by_hand(monkeypatch):
    spans, ends = _timeline()
    reader = registry.Registry().module("readers", "cycle_account")
    found = reader.cycles(spans, (0.0, ends[-1] + 5))
    assert len(found) == 100
    assert sorted({b - a for a, b, _ in found}) == [PLAIN, BEHIND]
    values = _read_all(_ctx(spans, (0.0, ends[-1] + 5), monkeypatch))
    # sorted: 10 zeros [0, 10), 360 plain [10, 370), 40 behind [370, 410);
    # the band [369, 405.9) holds 1 plain gap and 35.9 behind a prefill
    width, plain, behind = 36.9, 1.0, 35.9
    time = plain * PLAIN + behind * BEHIND
    assert values["sched.gap_tail_mean.serve"] == \
        pytest.approx(time / width / 1e6)
    assert values["sched.gap_tail_mean.serve"] == pytest.approx(
        clock.tail_mean([0] * 10 + [PLAIN] * 360 + [BEHIND] * 40) / 1e6)
    assert values["sched.gap_tail_prefill.serve"] == \
        pytest.approx(100 * behind * PREFILL / time)            # 84.6 %
    assert values["sched.gap_tail_decode.serve"] == \
        pytest.approx(100 * width * DECODE / time)              # 10.2 %
    assert values["sched.gap_tail_outside.serve"] == \
        pytest.approx(100 * width * OUTSIDE / time)             # 1.02 %
    assert values["sched.prefill_tokens_ahead_tail.serve"] == \
        pytest.approx(behind * 512 / width)                     # 498.1
    # the three shares and the rest (tail, admit, deliver) are the whole
    rest = 100 * width * (TAIL + ADMIT + DELIVER) / time
    shares = [values[f"sched.gap_tail_{p}.serve"]
              for p in ("prefill", "decode", "outside")]
    assert all(0.0 <= s <= 100.0 for s in shares)
    assert sum(shares) + rest == pytest.approx(100.0)


def test_a_cycle_that_straddles_the_windows_edge_is_left_out(monkeypatch):
    spans, ends = _timeline()
    # the window opens inside the first flush and closes inside the last
    window = (ends[0] - 1, ends[-1] - 1)
    reader = registry.Registry().module("readers", "cycle_account")
    found = reader.cycles(spans, window)
    assert len(found) == 98 and found[0][0] == ends[1] \
        and found[-1][1] == ends[-2]
    values = _read_all(_ctx(spans, window, monkeypatch))
    # 352 plain gaps now: 402 in all, the band [361.8, 397.98)
    time = 0.2 * PLAIN + 35.98 * BEHIND
    assert values["sched.gap_tail_mean.serve"] == \
        pytest.approx(time / 36.18 / 1e6)
    assert values["sched.gap_tail_prefill.serve"] == \
        pytest.approx(100 * 35.98 * PREFILL / time)


def test_flushes_that_delivered_nothing_or_failed_end_no_cycle():
    reader = registry.Registry().module("readers", "cycle_account")
    account = {"seq": 1, "gaps": 2, "requests": 2, "tokens": 2,
               "prefill_tokens": 0}
    spans = [
        PS.Span("serve.deliver", 10, 12, tuple(account.items())),
        # an idle step's flush: gaps=0 and nothing else
        PS.Span("serve.deliver", 30, 31, (("requests", 0), ("tokens", 0),
                                          ("gaps", 0))),
        # a flush whose journal raised: the span closed before the account
        PS.Span("serve.deliver", 50, 52, (("requests", 2), ("tokens", 2))),
        PS.Span("serve.deliver", 70, 72,
                tuple(dict(account, seq=2).items()))]
    found = reader.cycles(spans, (0, 100))
    assert [(a, b) for a, b, _ in found] == [(12, 72)]
    acc = reader.band_account(spans, (0, 100))
    assert acc["tail_mean"] == pytest.approx(60 / 1e6)
    assert acc["outside"] == acc["time"]        # no serve.step anywhere
    assert reader.band_account(spans[:3], (0, 100)) is None


def test_a_parent_without_the_facts_reads_nothing(monkeypatch):
    """The parent commit under this PR's benchmark files: its
    ``serve.deliver`` carries ``requests`` and ``tokens`` alone."""
    spans, ends = _timeline(account=False)
    assert _read_all(_ctx(spans, (0.0, ends[-1] + 5), monkeypatch)) == \
        dict.fromkeys(METRICS)
    assert _read_all(_ctx([], (0.0, 100.0), monkeypatch)) == \
        dict.fromkeys(METRICS)


def test_the_reduction_is_made_once_a_run(monkeypatch):
    spans, ends = _timeline()
    calls = []
    monkeypatch.setattr(PS, "of_run",
                        lambda root=None: calls.append(1) or spans)
    ctx = types.SimpleNamespace(trace=T.Trace({}, [], (0.0, ends[-1] + 5)))
    assert None not in _read_all(ctx).values()
    assert calls == [1]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_files_are_named_and_listed_as_the_issue_says(name):
    reg = registry.Registry()
    unit, better, what = METRICS[name]
    spec = reg.layer_metric(name)
    entry = reg._entry("per_layer", name)
    assert (spec["reader"], spec["args"]) == ("cycle_account",
                                              {"what": what})
    for doc in (spec, entry):
        assert (doc["unit"], doc["better"], doc["source"], doc["layer"],
                doc["moves"]) == (unit, better, "program_span", LAYER,
                                  "itl_tail_mean_ms")
    assert entry["workloads"] == CELLS
    # appended after every accepted entry
    names = [m["name"] for m in reg.benchmark["per_layer"]]
    assert set(names[-5:]) == set(METRICS)
    # not an idle share: the partition of the idle time is untouched
    assert "serve.deliver" in PS.listed_spans(reg)
    assert len(PS.listed_spans(reg)) == 11
