"""A CPU rehearsal of the harness: the benchmark's files, the traffic
generator, the refusal to run off the chip, and whole runs of cells at
test size, clean and with the timed path broken underneath.

The runs skip only the harness's look for a chip (and the peak table's
device kind, the persistent cache and the chip trace, which the CPU
lacks); everything else is the code a chip run executes."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import controls, generator, harness
from chipbench.tests import tiny

CHECKOUT = tiny.CHECKOUT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def test_every_cell_names_a_config_and_a_mix_that_exist():
    bench = _bench()
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"])
    for c in configs.values():
        assert c["file"].startswith("chipbench/")
        cfg = json.loads((CHECKOUT / c["file"]).read_text())
        assert (CHECKOUT / "chipbench" / "drivers"
                / f"{cfg['system']}.py").is_file()
        assert (CHECKOUT / "chipbench" / "reference"
                / f"{cfg['reference']}.py").is_file()
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells)
    for w in bench["workloads"]:
        assert w["config"] in configs
        generator.load_mix(CHECKOUT / "chipbench", w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        for name in (w["name"], w["config"], w["traffic"]):
            assert NAME.match(name), name


def test_every_metric_is_reported_where_it_is_listed():
    bench = _bench()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    metrics = CHECKOUT / "chipbench" / "metrics"
    for m in bench["per_layer"]:
        assert (metrics / f"{m['name']}.py").is_file() or (
            metrics / f"{m['name'].split('.')[0]}.py").is_file()
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline") or "roofline" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        own = [m for m in bench["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert len(own) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_closed_groups_repeat_for_a_seed():
    mix = {"loop": "closed", "group": 16, "prompt_lens": [512, 128, 768,
                                                         256],
           "new_tokens": 128}
    take = lambda seed: [next(g) for g in [generator.closed_groups(
        mix, seed, 32)] for _ in range(5)]
    a, b, c = take(2 ** 31 + 3), take(2 ** 31 + 3), take(7)
    assert a == b
    assert [len(x) for x in a] == [16] * 5
    assert [x[0].prompt_len for x in a] == [512, 128, 768, 256, 512]
    assert [x[0].prompt_len for x in c] == [x[0].prompt_len for x in a]
    assert [r.item for r in a[0]] != [r.item for r in c[0]]


def test_run_exits_nonzero_on_the_cpu_and_names_it():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mri-cine.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr and "TPU" in p.stderr
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def cpu(monkeypatch):
    """The harness without its look for a chip, the peak table's device
    kind, the persistent cache or a chip trace (a recorded one stands in)."""
    for owner, name, value in tiny.cpu_patches():
        monkeypatch.setattr(owner, name, value)


run = tiny.run


E2E = {"tiny.stream": "mri_scans_per_s", "tiny.offline": "lm_tokens_per_s"}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_a_cell_added_from_files_alone_runs_correct(root, cpu, cell):
    res = run(root, cell)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", E2E[cell]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("cell", sorted(E2E))
def test_traced_run_reports_the_per_layer_metrics(root, cpu, cell):
    res = run(root, cell, trace=1, seconds=2.0)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m["workloads"]}
    assert set(res["metrics"]) <= listed
    assert res["metrics"], "no per-layer metric was read"
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"] is True


def test_a_metric_added_from_files_alone_is_reported(root, cpu, tmp_path):
    new = tmp_path / "bench"
    shutil.copytree(root, new)
    (new / "chipbench" / "metrics" / "groups_run.stream.py").write_text(
        "def read(r):\n    return float(len(r.counters['groups']))\n")
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "groups_run.stream", "unit": "groups", "better": "higher",
        "source": "host_clock", "layer": "stream executor and arena",
        "moves": "mri_scans_per_s", "workloads": ["tiny.stream"]})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run(new, "tiny.stream", trace=1, seconds=2.0)
    assert res["metrics"]["groups_run.stream"]["value"] >= 1


def test_an_answer_altered_where_it_is_produced_is_caught(
        root, cpu, monkeypatch):
    from repro.core import graph
    orig = graph.Pipeline.run

    def altered(self, *a, **kw):
        outs = orig(self, *a, **kw)
        for o in outs if isinstance(outs, list) else [outs]:
            o.get_ndarray(0).host[...] *= 1.001
        return outs
    monkeypatch.setattr(graph.Pipeline, "run", altered)
    res = run(root, "tiny.stream")
    assert res["correct"] is False
    assert res["checks"]["mri_max_rel_err"]["value"] > 5e-4


def test_a_token_altered_where_it_is_produced_is_caught(
        root, cpu, monkeypatch):
    from repro.serve import pipeline
    orig = pipeline.LMServer.step

    def altered(self):
        orig(self)
        for r in self.results:
            if r:
                r[-1] = (r[-1] + 1) % tiny.LM["vocab"]
    monkeypatch.setattr(pipeline.LMServer, "step", altered)
    res = run(root, "tiny.offline")
    assert res["correct"] is False
    assert res["checks"]["lm_max_logit_gap"]["value"] > \
        tiny.LM["limits"]["lm_max_logit_gap"]


@pytest.mark.parametrize("cell,check", [("tiny.stream", "mri_max_rel_err"),
                                        ("tiny.offline", "lm_max_logit_gap")])
def test_the_control_in_the_program_s_place_is_not_correct(
        root, cpu, cell, check):
    """The reference one precision down, in the timed path's place (images
    of bfloat16 arrays; an LMServer serving float8-rounded weights), fails
    the configuration's own limit through the harness's comparison."""
    system = "mri" if cell == "tiny.stream" else "lm"
    with controls.in_place(system):
        res = run(root, cell)
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]
    assert res["checks"][check]["limit"] == (
        tiny.MRI if system == "mri" else tiny.LM)["limits"][check]


def test_the_four_chip_twin_reports_the_four_chip_cell_s_names(tmp_path):
    """A stream sharded over four (forced CPU) devices reports the end-to-
    end metric under the four-chip cell's own name, and each per-layer
    metric listed for it, read by the reader of its quantity."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip())
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.four_chip_twin",
         str(tmp_path)], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    plain, traced = [json.loads(x) for x in p.stdout.splitlines()[-2:]]
    bench = _bench()
    for res in (plain, traced):
        assert res["correct"] is True, res["checks"]
        assert res["device"]["count"] == 4
        assert res["checks"]["chips_without_output"]["value"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if "mri-cine.stream-4chip" in m.get("workloads", ["all"])
            or "workloads" not in m}
    assert set(plain["metrics"]) == want == {"setup_s",
                                             "mri_scans_per_s.4chip"}
    listed = {m["name"] for m in bench["per_layer"]
              if "mri-cine.stream-4chip" in m["workloads"]}
    assert set(traced["metrics"]) == listed
    assert all(v["value"] > 0 for v in traced["metrics"].values())


def test_every_prefill_shape_is_built_in_set_up(root, cpu, monkeypatch):
    """The LM set-up warms each prompt length of the cycle, also where the
    cycle is longer than the batch, so nothing is built in the window."""
    from repro.serve import pipeline
    orig = pipeline.LMServer._prefill_pipe
    built = []

    def spy(self, key):
        if key not in self._prefill_pipes:
            built.append((key, self.sampling.max_new_tokens))
        return orig(self, key)
    monkeypatch.setattr(pipeline.LMServer, "_prefill_pipe", spy)
    assert run(root, "tiny.offline")["correct"] is True
    mix = tiny.MIXES["t-offline"]
    assert len(mix["prompt_lens"]) > tiny.LM["batch"]
    assert sorted(k for k, _ in built) == sorted(mix["prompt_lens"])
    assert all(n != mix["new_tokens"] for _, n in built)
