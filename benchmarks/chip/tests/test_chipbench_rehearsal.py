"""CPU rehearsals of every cell, and of the city cells held out of the
benchmark (``fixtures/city``), the refusal of a machine with no TPU, and
the check's control and faults: each planted in the timed path of a run
at a tiny size must turn ``correct`` false.  A cell's sizes and faults
come from its own file, ``rehearse/<cell>.json``, and its faults from its
entry's ``faults/<entry>.py``, so a cell joins with files alone.

Each run is its own process (``drive.py``), so that a four-device cell can
ask XLA for four CPU devices before JAX starts."""
import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
SPEC = os.path.join(ROOT, "BENCHMARK.json")
HELD = os.path.join(HERE, "fixtures", "city", "spec.json")
#: one file per cell, ``<cell>.json``: ``config`` and ``mix`` overrides,
#: the window's ``seconds`` and the ``faults`` planted in it.  Sizes a CPU
#: holds; the widths are the cells' own.  The camera calibration blocks
#: hold thousands of images, so their most extreme estimates (which the
#: check compares) reach the range where the control's bfloat16 estimates
#: saturate; the city serves enough ticks that its policy windows (512
#: estimates per district) fill and wrap.
REHEARSE = os.path.join(HERE, "rehearse")
#: a CPU run reads the host's clock and the program's counters; its trace
#: holds no TPU plane, so metrics of the device trace stay silent
CPU_SOURCES = {"host_clock", "program_counter"}


def _json(path):
    with open(path) as f:
        return json.load(f)


def rehearsals(directory=REHEARSE):
    """Each rehearsal file's content, by its stem: the cell's name."""
    return {f[:-len(".json")]: _json(os.path.join(directory, f))
            for f in sorted(os.listdir(directory)) if f.endswith(".json")}


def unrehearsed(specs, directory=REHEARSE):
    """Cells of ``specs`` with no rehearsal file, and rehearsal files that
    name no cell."""
    cells = {c["name"] for spec in specs for c in spec["workloads"]}
    files = set(rehearsals(directory))
    return cells - files, files - cells


def spec_of(cell):
    spec = _json(SPEC)
    return spec if any(c["name"] == cell for c in spec["workloads"]) else _json(HELD)


def cpu_readable(spec, cell):
    """Per-layer metrics of ``cell`` that a run on the CPU reports."""
    return {m["name"] for m in spec["per_layer"]
            if cell in m.get("workloads", [cell]) and m["source"] in CPU_SOURCES}


REHEARSALS = rehearsals()


def _env(chips):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return env


def drive(cell, trace=0, fault=""):
    r = REHEARSALS[cell]
    chips = next(int(c["chips"]) for c in spec_of(cell)["workloads"] if c["name"] == cell)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), cell, json.dumps(r["config"]),
         json.dumps(r["mix"]), str(trace), str(r["seconds"])] + ([fault] if fault else []),
        env=_env(chips), capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_every_cell_is_rehearsed():
    assert unrehearsed([_json(SPEC), _json(HELD)]) == (set(), set())


def test_rehearsal_found_by_the_cells_name(tmp_path):
    spec = {"workloads": [{"name": "probe.cell", "config": "probe", "traffic": "probe",
                           "chips": 1}]}
    rehearse = tmp_path / "rehearse"
    rehearse.mkdir()
    assert unrehearsed([spec], str(rehearse)) == ({"probe.cell"}, set())
    probe = {"config": {}, "mix": {"rate": 1}, "seconds": 1.0, "faults": ["estimate"]}
    (rehearse / "probe.cell.json").write_text(json.dumps(probe))
    assert rehearsals(str(rehearse)) == {"probe.cell": probe}
    assert unrehearsed([spec], str(rehearse)) == (set(), set())
    (rehearse / "gone.cell.json").write_text(json.dumps(probe))
    assert unrehearsed([spec], str(rehearse)) == (set(), {"gone.cell"})


@pytest.mark.parametrize("cell", ["cam_coco.poisson", "city.ticks", "city.ticks4"])
def test_cpu_readable_metrics_follow_their_source(cell):
    # the set the rehearsal named by hand before metrics were sorted by source
    by_hand = {"serve_ms_per_kframe", "decide_p95_ms", "dispatch_ms_per_kframe",
               "featurize_ms_per_kframe", "fleet_decide_ms_per_kframe", "compiles_in_window"}
    spec = spec_of(cell)
    names = {m["name"] for m in spec["per_layer"] if cell in m.get("workloads", [cell])}
    assert cpu_readable(spec, cell) == names & by_hand


@pytest.mark.parametrize("entry", ["session", "fleet"])
def test_faults_found_by_the_cells_entry(entry):
    import drive
    from faults import detector
    assert importlib.import_module(f"faults.{entry}").plant is detector.plant
    with pytest.raises(ValueError, match="unknown fault"):
        drive.plant(entry, "no_such_fault")


@pytest.mark.parametrize("cell", sorted(REHEARSALS))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_correct(cell, trace):
    res = drive(cell, trace)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    spec = spec_of(cell)
    names = {m["name"] for m in spec["per_layer" if trace else "end_to_end"]
             if cell in m.get("workloads", [cell])}
    want = cpu_readable(spec, cell) if trace else names
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for k, m in res["metrics"].items() if k != "compiles_in_window")


@pytest.mark.parametrize("cell,fault", [(cell, fault) for cell, r in sorted(REHEARSALS.items())
                                        for fault in r["faults"]])
def test_fault_turns_correct_false(cell, fault):
    res = drive(cell, 0, fault)
    assert res["correct"] is False, res["checks"]


def test_run_refuses_a_machine_without_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload", "cam_coco.poisson",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        env=_env(1), capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_spec_names_only_files_under_paths():
    spec = _json(SPEC)
    assert spec["paths"] == ["benchmarks/chip"]
    for c in spec["configs"]:
        assert c["file"].startswith("benchmarks/chip/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(CHIP, "traffic", w["traffic"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(CHIP, "metrics", m["name"] + ".py"))
