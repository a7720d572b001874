"""The reduction from a profiler trace to the program's layers (named device
scopes, ``fedsllm.*`` host spans, idle time by span) and the per-layer
readers: on hand-made events, and on small traces recorded on a v5e."""

import os

import pytest

from harness import layers, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "v5e_small.xplane.pb")
SCOPED = os.path.join(DATA, "v5e_scoped.xplane.pb")
NEW_READERS = ["client_ms", "server_ms", "merge_ms", "aggregate_ms", "attention_ms",
               "ssd_ms", "campaign_host_ms", "idle_campaign_ms"]


def hand_made() -> layers.LayerTrace:
    """One round program (7) and one unscoped program (9) on one device,
    inside a campaign's spans; idle gaps [0, 100), [400, 20.4k), [50.7k, 90k)
    and [100k, 200k)."""
    client = "jit(f)/fedsllm.client/dot_general:"
    merge_bwd = "jit(f)/transpose(jvp(fedsllm.client))/lora.merge/add:"
    server = "jit(f)/fedsllm.server/dot_general:"
    return layers.LayerTrace(
        ops={"/device:TPU:0": [(100, 300, "fusion.1", 7, client),
                               (300, 400, "fusion.2", 7, merge_bwd),
                               (20_400, 50_700, "fusion.3", 7, server),
                               (90_000, 100_000, "fusion.1", 9, "")]},
        modules={"/device:TPU:0": [(100, 50_700, 7), (90_000, 100_000, 9)]},
        host=[(0, 200_000, "bench.window"), (10_000, 11_000, "D2H_Dispatch")],
        spans=[(0, 200_000, "fedsllm.campaign", {}),
               (0, 199_000, "fedsllm.round", {"round": 0}),
               (0, 90, "fedsllm.plan", {}),
               (95, 120, "fedsllm.dispatch", {}),
               (120, 60_000, "fedsllm.read", {}),
               (60_000, 95_000, "fedsllm.batches", {})])


def with_spans_as_host(lt: layers.LayerTrace) -> layers.LayerTrace:
    lt.host += [(s, e, n) for s, e, n, _ in lt.spans]
    return lt


def test_in_path_matches_whole_components_and_transforms():
    assert layers.in_path("fedsllm.client", "jit(f)/vmap(fedsllm.client)/dot:")
    assert layers.in_path("fedsllm.client", "jit(f)/transpose(jvp(fedsllm.client))/add")
    assert layers.in_path("lora.merge", "jit(f)/fedsllm.server/lora.merge")
    assert not layers.in_path("fedsllm.client", "jit(f)/fedsllm.clients/dot:")
    assert not layers.in_path("model.ssd", "")


def test_scope_seconds_are_inclusive_self_times():
    lt = with_spans_as_host(hand_made())
    self_s = layers.op_self_times(lt, 0, 200_000)
    scopes = layers.scope_seconds(self_s)
    assert scopes["fedsllm.client"] == pytest.approx(300e-9)
    assert scopes["lora.merge"] == pytest.approx(100e-9)
    assert scopes["fedsllm.server"] == pytest.approx(30_300e-9)
    assert scopes[layers.UNSCOPED] == pytest.approx(10_000e-9)
    assert "model.attention" not in scopes
    # one fusion name in two programs: two ops, each keyed by its program
    assert self_s[("fusion.1", 7, "jit(f)/fedsllm.client/dot_general:")] == pytest.approx(200e-9)


def test_idle_by_span_places_gaps_in_the_innermost_span():
    lt = with_spans_as_host(hand_made())
    idle = layers.idle_by_span(lt, 0, 200_000)
    assert idle == pytest.approx({
        ("fedsllm.plan", "(gaps under 10 us)"): [100e-9, 1],
        ("fedsllm.read", "D2H_Dispatch"): [20_000e-9, 1],
        ("fedsllm.batches", "(no host event)"): [39_300e-9, 1],
        ("fedsllm.round", "(no host event)"): [100_000e-9, 1]})


def test_keys_clock_and_coverage_on_hand_made_events():
    lt = with_spans_as_host(hand_made())
    k = layers.keys(lt)
    assert set(k) == {"scope_s", "span_s", "idle_span_s"}
    assert layers.UNSCOPED not in k["scope_s"]
    assert k["span_s"]["fedsllm.read"] == pytest.approx(59_880e-9)
    assert k["idle_span_s"]["fedsllm.read"] == pytest.approx(20_000e-9)
    assert layers.round_program(layers.op_self_times(lt, 0, 200_000)) == 7
    assert layers.clock_check(lt, 0, 200_000, 7) == [(5, 9_300)]
    share, outside = layers.coverage(layers.op_self_times(lt, 0, 200_000), 7)
    assert share == pytest.approx(1.0) and outside == []


def test_keys_leave_out_what_the_trace_lacks():
    lt = layers.LayerTrace(ops={"/device:TPU:0": [(100, 300, "fusion", 1, "jit(f)/dot:")]},
                           host=[(0, 1000, "bench.window")])
    assert layers.keys(lt) == {}


def test_op_paths_read_the_recorded_metadata():
    paths = layers.op_paths(SMALL)
    assert paths[("/device:TPU:0", 18084989565708003084, "convolution_tanh_fusion")] == \
        "jit(<lambda>)/dot_general:"


def test_reduce_on_the_small_fixture_is_unchanged():
    red = trace.reduce(trace.load(SMALL))
    assert red["busy_s"] == pytest.approx(7.2614e-05)
    assert red["window_s"] == pytest.approx(0.010305679)
    want = {"device_ops": [["fusion", 3.7847e-05], ["convolution_tanh_fusion", 3.4719e-05],
                           ["copy-start", 3.9e-08], ["copy-done", 9e-09]],
            "idle_gaps": [["bench.run (2 gaps)", 0.006410903],
                          ["$time sleep (2 gaps)", 0.003822149],
                          ["(gaps under 10 us) (7 gaps)", 1.3e-08]]}
    for part, rows in want.items():
        got = red["breakdown"][part]
        assert [n for n, _ in got] == [n for n, _ in rows]
        assert [s for _, s in got] == pytest.approx([s for _, s in rows])


def test_existing_readers_on_the_small_fixture_are_unchanged():
    red = trace.reduce(trace.load(SMALL))
    m = {"busy_s": red["busy_s"], "window_s": red["window_s"], "rounds": 3,
         "model_flops": 6e9, "peak_flops": 197e12}
    m.update(layers.keys(layers.load(SMALL)))
    got = {n: spec.module("metrics", n).read(m)
           for n in ("device_idle_share", "mfu", "round_device_ms", "host_ms_per_round")}
    assert got == pytest.approx({"device_idle_share": 100 * (1 - 7.2614e-05 / 0.010305679),
                                 "mfu": 100 * 6e9 / (0.010305679 * 197e12),
                                 "round_device_ms": 1e3 * 7.2614e-05 / 3,
                                 "host_ms_per_round": 1e3 * (0.010305679 - 7.2614e-05) / 3})


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_is_none_without_its_input(name):
    read = spec.module("metrics", name).read
    assert read({"busy_s": 1.0, "window_s": 1.0, "rounds": 2}) is None
    assert read({"rounds": 2, "scope_s": {}, "span_s": {"fedsllm.read": 1.0}}) is None


@pytest.mark.parametrize("name,want", [
    ("client_ms", 250.0), ("server_ms", 500.0), ("merge_ms", 50.0), ("aggregate_ms", 5.0),
    ("attention_ms", 100.0), ("ssd_ms", None), ("campaign_host_ms", 40.0),
    ("idle_campaign_ms", 3.0)])
def test_new_reader_on_a_hand_made_m(name, want):
    m = {"rounds": 2,
         "scope_s": {"fedsllm.client": 0.5, "fedsllm.server": 1.0, "lora.merge": 0.1,
                     "fedsllm.aggregate": 0.01, "model.attention": 0.2},
         "span_s": {"fedsllm.campaign": 2.0, "fedsllm.read": 1.92},
         "idle_span_s": {"fedsllm.plan": 0.004, "fedsllm.batches": 0.002,
                         "fedsllm.read": 0.5, layers.NO_SPAN: 0.7}}
    got = spec.module("metrics", name).read(m)
    assert got == (None if want is None else pytest.approx(want))


@pytest.fixture(scope="module")
def scoped():
    lt = layers.load(SCOPED)
    return lt, layers.window(lt), trace.reduce(trace.load(SCOPED))


def test_scoped_fixture_scopes_and_unscoped_sum_to_busy(scoped):
    lt, (lo, hi), red = scoped
    scopes = layers.scope_seconds(layers.op_self_times(lt, lo, hi))
    assert scopes["fedsllm.client"] > 0 and scopes["fedsllm.server"] > 0
    assert sum(scopes.values()) == pytest.approx(red["busy_s"])


def test_scoped_fixture_idle_by_span_and_busy_fill_the_window(scoped):
    lt, (lo, hi), red = scoped
    idle = layers.idle_by_span(lt, lo, hi)
    assert sum(s for s, _ in idle.values()) + red["busy_s"] == pytest.approx(red["window_s"])
    k = layers.keys(lt)
    assert k["idle_span_s"]["fedsllm.plan"] > 0.001  # the 2-ms sleep a round


def test_scoped_fixture_rounds_and_clock(scoped):
    lt, (lo, hi), _ = scoped
    rounds = [st["round"] for _, _, n, st in sorted(lt.spans) if n == "fedsllm.round"]
    assert rounds == [0, 1, 2]
    prog = layers.round_program(layers.op_self_times(lt, lo, hi))
    clock = layers.clock_check(lt, lo, hi, prog)
    assert len(clock) == 3
    # each run fits between its round's dispatch start and read end; on
    # this trace the device's events sit 1.3-1.4 ms before the host's, so
    # the lead alone is negative
    assert all(lead + tail > 0 for lead, tail in clock)
