"""The harness finds configurations, mixes, cells and metric readers by
name, picks up new files without an edit to any file already there, keeps
names and units to the allowed characters, and refuses to measure
without a TPU."""
import copy
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_cell_resolves_by_name():
    bm = run.benchmark()
    for w in bm["workloads"]:
        cfg = run.config_file(bm, w["config"])
        mix = run.mix_file(w["traffic"])
        data = run.cell_file(w["name"])
        assert cfg["name"] == w["config"] and mix["name"] == w["traffic"]
        assert data["max_logit_gap"]["limit"] > 0
        assert callable(run.module("references", cfg["reference"]).forward)
    for m in bm["per_layer"]:
        assert callable(run.module("metrics", m["name"]).read)


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_mix_and_metric_files_are_picked_up(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    before = _digest(bench)
    mix = dict(run.mix_file("codegen"), name="bursty")
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "metrics" / "steps_traced.py").write_text(
        "def read(ctx):\n    return float(len(ctx['steps'])) or None\n")
    assert run.mix_file("bursty", str(bench))["name"] == "bursty"
    reader = run.module("metrics", "steps_traced", str(bench))
    assert reader.read({"steps": [{}, {}]}) == 2.0
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_names_units_and_paths_keep_to_the_rules():
    bm = run.benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bm["configs"]]
    names += [w["name"] for w in bm["workloads"]]
    names += [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    names += [w["traffic"] for w in bm["workloads"]]
    names += [k for c in bm["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = bm["end_to_end"] + bm["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"] for m in bm["end_to_end"]}
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                           f"{m['name']}.py"))
    for c in bm["configs"]:
        assert c["file"].startswith(bm["paths"][0] + "/")
    for text in ([w["why"] for w in bm["workloads"]]
                 + [c["why"] for c in bm["configs"]]
                 + [m["layer"] for m in bm["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_measuring_path_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = run.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload", w,
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_every_seed_asks_for_the_same_work_in_its_own_order():
    """Two seeds draw the same lengths and arrival gaps in another order;
    one seed draws the same requests every time; a mix with a rate adds
    Poisson arrivals after the burst."""
    from bench import traffic
    mix = copy.deepcopy(run.mix_file("codegen"))
    mix["arrivals"].update(burst=4, rate_per_s=2.0)
    a, b = (traffic.requests(mix, s, 10.0, 512) for s in (2 ** 31 + 7, 11))
    assert traffic.requests(mix, 11, 10.0, 512) == b
    assert len(a) == len(b) == traffic.n_requests(mix, 10.0)
    assert [r["due_s"] for r in a[:4]] == [0.0] * 4
    for key in ("max_new", "due_s"):
        assert [r[key] for r in a] != [r[key] for r in b]
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    gaps = [sorted(y["due_s"] - x["due_s"] for x, y in zip(r[3:], r[4:]))
            for r in (a, b)]
    assert gaps[0] == pytest.approx(gaps[1])
    assert min(gaps[0]) > 0
