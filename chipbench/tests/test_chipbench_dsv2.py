"""The DeepSeek-V2-Lite cell's twin at test size on the CPU, its plain
reference against the program, and the hand counts of its costs.

The twin is added to the tiny benchmark root the way a cell is added:
a configuration with the published file's keys at test widths, a mix, a
cell, and the cell's name appended to the metrics the real cell lists."""
import json

import jax
import numpy as np
import pytest

from chipbench import costs, costs_moe
from chipbench.drivers import lm_moe
from chipbench.peaks import PEAKS
from chipbench.reference import mla_moe_decoder as ref
from chipbench.tests import tiny
from chipbench.tools import control_moe

REAL = json.loads((tiny.BENCH / "configs" / "deepseek-v2-lite.json")
                  .read_text())
CELL, TWIN = "tiny.offline-moe", "deepseek-v2-lite.offline-64"
#: the published keys at test widths; the limits and precision are the
#: real configuration's
MOE = dict(REAL, name="dsv2-tiny", hidden_size=64, intermediate_size=96,
           kv_lora_rank=32, moe_intermediate_size=32, n_routed_experts=16,
           num_attention_heads=4, num_key_value_heads=4,
           num_hidden_layers=3, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, vocab_size=512, n_experts=8, expert_offset=4,
           batch=8, max_len=48)
MIX = {"loop": "closed", "group": 8, "prompt_lens": [24, 8, 16, 12],
       "new_tokens": 16}
F32 = dict(MOE, param_dtype="float32", dtype="float32")
V5E = PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs" / "dsv2-tiny.json").write_text(
        json.dumps(MOE))
    (root / "chipbench" / "traffic" / "t-offline-moe.json").write_text(
        json.dumps(MIX))
    bench["configs"].append({"name": "dsv2-tiny", "source": "test",
                             "file": "chipbench/configs/dsv2-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "dsv2-tiny",
                               "traffic": "t-offline-moe", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if TWIN in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def cpu(monkeypatch):
    for owner, name, value in tiny.cpu_patches():
        monkeypatch.setattr(owner, name, value)


def test_the_tiny_deepseek_cell_runs_correct(root, cpu):
    res = tiny.run(root, CELL)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "lm_tokens_per_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_a_token_altered_in_the_deepseek_cell_is_caught(
        root, cpu, monkeypatch):
    from repro.serve import pipeline
    orig = pipeline.LMServer.step

    def altered(self):
        orig(self)
        for r in self.results:
            if r:
                r[-1] = (r[-1] + 1) % MOE["vocab_size"]
    monkeypatch.setattr(pipeline.LMServer, "step", altered)
    res = tiny.run(root, CELL)
    assert res["correct"] is False
    assert res["checks"]["lm_max_logit_gap"]["value"] > \
        MOE["limits"]["lm_max_logit_gap"]


def test_traced_deepseek_run_reports_its_seven_per_layer_metrics(
        root, cpu, monkeypatch):
    """The CPU has no chip trace: the recorded one stands in for the
    summary, and fixed device times for the decode program's grouped
    matmuls, which the recorded trace lacks.  The two readers of the
    program's spans report where the spans pair with the driver's step
    records (``window_calls``), as they did in every chip run; on a
    loaded CPU a step preempted outside its ``lm.step`` span unpairs
    them, and then they report nothing."""
    from types import SimpleNamespace
    from chipbench.metrics.window_compiles import window_calls
    monkeypatch.setattr(lm_moe, "decode_program_ops", lambda tracer: {
        "decode_s": 0.05, "decode_calls": 1.0, "grouped_s": 0.02})
    outcomes = []
    run = lm_moe.run
    monkeypatch.setattr(lm_moe, "run",
                        lambda cell: outcomes.append(run(cell)) or outcomes[-1])
    res = tiny.run(root, CELL, trace=1, seconds=2.0)
    assert res["correct"] is True, res["checks"]
    paired = window_calls(SimpleNamespace(counters=outcomes[-1].counters))
    spans = {"window_compiles.dsv2", "decode_host_ms.dsv2"}
    assert set(res["metrics"]) == {
        "idle_share.dsv2", "decode_roofline.dsv2", "lm_mfu.dsv2",
        "moe_roofline.dsv2", "admit_share.dsv2"} | (spans if paired else set())
    assert all(isinstance(v["value"], (int, float))
               for v in res["metrics"].values())
    if paired:
        assert res["metrics"]["window_compiles.dsv2"]["value"] == 0


@pytest.mark.parametrize("quantity", ["admit_share", "decode_host_ms",
                                      "window_compiles"])
def test_the_deepseek_lm_server_readers_read_the_lm_quantities(
        monkeypatch, quantity):
    """The cell's `lm server` metrics read what danube's do, from the same
    step records and spans (the span readers' synthetic steps)."""
    from chipbench.tests import test_chipbench_span_readers as sr
    counters = {"steps": sr.STEPS, "window_s": 1.0}
    calls = {"lm.step": sr.STEP_ROOTS}
    want = sr._read(f"{quantity}.lm", counters, calls, monkeypatch)
    assert want is not None
    assert sr._read(f"{quantity}.dsv2", counters, calls, monkeypatch) == want


@pytest.mark.parametrize("key,value", [("routed_scaling_factor", 16.0),
                                       ("topk_method", "group_limited_greedy")])
def test_a_configuration_the_layer_does_not_do_is_refused(key, value):
    """The program's layer neither scales the gates nor limits the top-k
    to groups of experts: the full DeepSeek-V2's values of those keys
    are refused at once, naming the key."""
    with pytest.raises(ValueError, match=key):
        lm_moe.arch_config(dict(MOE, **{key: value}))


def test_the_float8_control_in_the_program_s_place_is_not_correct(root, cpu):
    """Float8 weights in the program's place fail the configuration's
    limit through the harness's comparison."""
    with control_moe.in_place("float8"):
        res = tiny.run(root, CELL)
    assert res["correct"] is False, res["checks"]
    c = res["checks"]["lm_max_logit_gap"]
    assert c["value"] > c["limit"] == MOE["limits"]["lm_max_logit_gap"]


@pytest.fixture(scope="module")
def own_gap(root):
    """The program's own reading of the default seed's sample."""
    with pytest.MonkeyPatch.context() as mp:
        for owner, name, value in tiny.cpu_patches():
            mp.setattr(owner, name, value)
        return tiny.run(root, CELL)["checks"]["lm_max_logit_gap"]["value"]


@pytest.mark.parametrize("kind", ["renorm", "no_yarn"])
def test_a_departure_of_the_mechanism_reads_a_wider_gap(root, cpu, own_gap,
                                                        kind):
    """The program with its gates renormalised, or without YaRN, in the
    program's place: the same seed's sample lies further from the
    reference than the program's own."""
    own = own_gap
    with control_moe.in_place(kind):
        res = tiny.run(root, CELL)
    assert res["checks"]["lm_max_logit_gap"]["value"] > own


def test_float32_reference_matches_prefill_then_decode():
    """The program's prefill of a prompt and its decode steps through the
    cache give the reference's full-forward logits at every position:
    float32 on both sides, so the tolerance covers summation order and
    the absorbed decode form (2e-4 of logits of order one)."""
    from repro.models import build_model
    model = build_model(lm_moe.arch_config(F32))
    w = ref.make_weights(F32, 2 ** 32 + 13)
    lm_moe.check_layout(model, w)
    toks = np.random.default_rng(1).integers(0, F32["vocab_size"], (1, 20))
    want = np.asarray(ref.logits(F32, w, toks))[0]
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(1, F32["max_len"])
        lg, cache = jax.jit(model.prefill)(w, toks[:, :12], cache)
        got = [np.asarray(lg)[0, 0]]
        for t in range(12, 20):
            lg, cache = jax.jit(model.decode_step)(
                w, toks[:, t:t + 1], np.int32(t), cache)
            got.append(np.asarray(lg)[0, 0])
    np.testing.assert_allclose(np.stack(got), want[11:], rtol=2e-4,
                               atol=2e-4)
    counts = np.asarray(cache["moe_counts"])[0]   # 2 expert layers
    assert counts[0] == 2 * 20 * 6 and counts[2] == 2 * 20
    assert 0 < counts[1] < counts[0]


#: at this size the bf16 program's widest gap read 0.0087-0.032 and the
#: float8 control's 0.56-0.81 over seeds 0-2 (CPU); the limit lies between
TEST_LIMIT = 0.2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lmserver_passes_and_float8_control_fails(seed):
    from repro.core import CLapp
    from repro.models import build_model
    from repro.serve import LMServer, SamplingConfig
    w = ref.make_weights(MOE, seed)
    server = LMServer(build_model(lm_moe.arch_config(MOE)),
                      jax.tree.map(np.asarray, w), batch=4, max_len=48,
                      sampling=SamplingConfig(max_new_tokens=24),
                      app=CLapp().init())
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, MOE["vocab_size"], 16).tolist()
               for _ in range(4)]
    for p in prompts:
        server.submit(p)
    served = server.run()
    ctrl = ref.control_weights(w)
    program = max(float(ref.served_gaps(MOE, w, p, r).max())
                  for p, r in zip(prompts, served))
    control = max(float(ref.control_gaps(MOE, w, ctrl, p, r).max())
                  for p, r in zip(prompts, served))
    assert program < TEST_LIMIT < control


def test_yarn_of_the_reference_follows_the_published_formulas():
    assert ref.yarn_low_high(REAL) == (10, 23)
    assert ref.softmax_scale(REAL) == pytest.approx(192 ** -0.5 * 1.5896,
                                                    rel=1e-4)
    cos, sin = ref.rope_tables(REAL, 5)
    assert np.allclose(cos ** 2 + sin ** 2, 1.0, atol=1e-6)  # amp 1


def test_deepseek_hand_counts():
    c = REAL
    attn = (2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 512 * 16 * 256
            + 16 * 128 * 2048)
    assert costs_moe.attn_matmul_params(c) == attn == 13_762_560
    assert costs_moe.expert_params(c) == 3 * 2048 * 1408 == 8_650_752
    assert costs_moe.total_params(c) == 1_805_714_432    # 3.61 GB of bf16
    held = 13 * 8 * 8_650_752
    assert costs_moe.held_expert_params(c) == held
    assert held * 2 == 1_799_356_416                     # 1.80 GB a step
    assert costs_moe.cache_bytes_per_token(c) == 14 * 576 * 2 + 14 * 4 \
        == 16_184
    router = 13 * 2048 * 64
    wbytes = (1_805_714_432 - 102400 * 2048 - router) * 2 + router * 4
    assert costs_moe.weight_bytes(c) == wbytes
    a = costs_moe.decode_step(c, 64, 500, 0.75)
    b = costs_moe.decode_step(c, 64, 501, 0.75)
    assert b.bytes - a.bytes == 64 * 16_184
    assert a.bytes == (wbytes + 64 * 2048 * 2 + 64 * 501 * 16_184
                       + 64 * 102400 * 4)
    n = (14 * attn + 3 * 2048 * 10944
         + 13 * (2048 * 64 + (2 + 0.75) * 8_650_752) + 2048 * 102400)
    assert costs_moe.active_params(c, 0.75) == n
    assert a.flops == 64 * 2 * n + 64 * 14 * 2 * 16 * (2 * 512 + 64) * 501
    assert a.bound(V5E) == "memory"
    e = costs_moe.held_experts_step(c, 624.0)
    assert e.flops == 6 * 2048 * 1408 * 624
    assert e.bytes == held * 2 + 624 * 2 * 2048 * 2
    assert costs_moe.held_per_row({"moe_rows": 10, "moe_held_assignments":
                                   7.5}) == 0.75
    assert costs_moe.held_per_row({"moe_rows": 0}) is None


@pytest.mark.parametrize("slower", [1.0, 1.5, 1e3])
def test_moe_shares_never_exceed_100_at_or_above_the_least_time(slower):
    for work in (costs_moe.decode_step(REAL, 64, 900, 0.75),
                 costs_moe.held_experts_step(REAL, 624.0)):
        t = work.least_time_s(V5E)
        assert costs.share_pct(t, t * slower) == pytest.approx(100 / slower)
