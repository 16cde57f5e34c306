"""Model families found by the configuration's ``model_type``: the llama
family as it was before the lookup; each cell's work counted by its own
family, on the cells of ``BENCHMARK.json`` and on fixture families' cells
added to a copy of the benchmark as new files and new entries only and run
as tiny fleet-train cells on the CPU; an unknown family refused by name."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import families  # noqa: E402
import program  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
from reference import llama as REF_LLAMA  # noqa: E402
from reference import tasks as REF_TASKS  # noqa: E402
from yardstick import flops as FLOPS  # noqa: E402

FLEET = run.load_module(BENCH / "drivers" / "fleet.py")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _parent_arch(config: dict):
    """``program.arch`` as it was before families were found by name."""
    from repro.configs import get_config
    from repro.configs.base import BlockSpec, StageSpec
    m = config["model"]
    return get_config(config["program_arch"]).replace(
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"],
        stages=(StageSpec(m["num_hidden_layers"],
                          (BlockSpec("attn", "mlp"),)),),
        param_dtype=m["torch_dtype"], compute_dtype=m["torch_dtype"])


def _clients(spec: dict) -> int:
    """The clients a round schedules."""
    topo, sched = spec["topology"], spec["schedule"]
    m = sched.get("participants_per_cell", 0)
    if sched["participation"] == "full" or not 0 < m < topo[
            "clients_per_cell"]:
        m = topo["clients_per_cell"]
    return topo["num_cells"] * m


def test_llama_arch_as_before():
    config = _config("smollm-135m")
    assert "model_type" not in config["model"]
    assert program.arch(config) == _parent_arch(config)


def test_llama_train_flops_is_the_yardsticks():
    model = _config("smollm-135m")["model"]
    assert REF_LLAMA.train_flops(model, 2, 256) == \
        FLOPS.train_flops(model, 2, 256)


# (scheduled clients, operations of one client) of the accepted cells:
# smollm-135m's 6 x 134479872 multiplying weights x 512 tokens plus 3 x its
# causal attention (yardstick.flops.train_flops, the llama family's count);
# the 1M cell's 6 x 610 weights x 10 samples (mlp_train_flops).  A change
# to either count shows here on the cells that read it.
ACCEPTED_WORK = {"smollm-135m.fleet-train": (16, 426764795904.0),
                 "fleet-1m-mlp.sync-cohort": (100000, 36600.0)}


@pytest.mark.parametrize("name", list(ACCEPTED_WORK))
def test_work_flops_as_before(name):
    clients, per = ACCEPTED_WORK[name]
    assert run.Cell(name).driver(2**31 + 5).work(7)["flops"] == \
        7 * clients * per


@pytest.fixture(scope="module")
def family_bench(tmp_path_factory):
    return tiny.add_fixture_cells(tmp_path_factory.mktemp("family"))


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]]
                         + list(tiny.FIXTURE_CELLS))
def test_work_flops_by_family(family_bench, name):
    """A round's work is its scheduled clients times the count of the
    cell's own family (``reference/<model_type>.py::train_flops``), or the
    MLP count where the configuration has no model."""
    bench, benchmark = tiny.cell_site(name, family_bench)
    cell = run.Cell(name, bench, benchmark)
    driver = cell.driver(2**31 + 5)
    task = driver.spec["task"]
    if "model" in cell.config:
        model = cell.config["model"]
        per = families.load("reference", model, bench).train_flops(
            model, task["local_batch"], task["seq_len"])
    else:
        dims = [task["feature_dim"], *task["hidden"], task["num_classes"]]
        per = FLOPS.mlp_train_flops(dims, task["local_batch"])
    assert driver.work(7)["flops"] == 7 * _clients(driver.spec) * per


def test_recount_is_not_the_llama_count(family_bench):
    """The fixture family that the per-cell tests run on counts work
    otherwise than the llama yardstick, so a test pinned to that count
    fails on it."""
    bench, benchmark = tiny.cell_site(tiny.RECOUNT_CELL, family_bench)
    cell = run.Cell(tiny.RECOUNT_CELL, bench, benchmark)
    model, task = cell.config["model"], cell.driver(1).spec["task"]
    counted = families.load("reference", model, bench).train_flops(
        model, task["local_batch"], task["seq_len"])
    assert counted != FLOPS.train_flops(model, task["local_batch"],
                                        task["seq_len"])


@pytest.mark.parametrize("kind", ["arch", "reference"])
def test_unknown_model_type_names_the_file(kind):
    config = _config("smollm-135m")
    config["model"] = {**config["model"], "model_type": "no_such_family"}
    spec = FLEET.resolve(config, json.loads(
        (BENCH / "traffic" / "fleet-train.json").read_text()))
    with pytest.raises(LookupError, match=str(
            BENCH / kind / "no_such_family.py")):
        if kind == "arch":
            program.arch(config)
        else:
            REF_TASKS.make(config, spec["task"])


def test_family_added_as_new_files_only(family_bench):
    """The fixture cells come as a ``model_config`` change brings a cell:
    every file of the benchmark stays byte for byte, and ``BENCHMARK.json``
    only gains entries."""
    bench, before, after, added = family_bench
    for path in added:
        assert not (BENCH / path.relative_to(bench)).exists(), path
    repo = [p for p in BENCH.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts and "fixtures" not in p.parts]
    assert len(repo) > 40
    for path in repo:
        copy = bench / path.relative_to(BENCH)
        assert copy.read_bytes() == path.read_bytes(), path
    assert after["workloads"][:len(before["workloads"])] == \
        before["workloads"]
    for key in ("end_to_end", "per_layer"):
        assert [m["name"] for m in after[key]] == \
            [m["name"] for m in before[key]]
        for old, new in zip(before[key], after[key]):
            listed = old.get("workloads")
            assert {k: v for k, v in new.items() if k != "workloads"} == \
                {k: v for k, v in old.items() if k != "workloads"}
            if listed is not None:
                assert new["workloads"][:len(listed)] == listed
                assert (tiny.RECOUNT_CELL in new["workloads"]) == \
                    ("tiny-llama.tiny-train" in listed)


def test_family_found_by_model_type(family_bench):
    bench, _, benchmark, _ = family_bench
    cell = run.Cell(tiny.FAMILY_CELL, bench, benchmark)
    driver = cell.driver(tiny.SEED)
    family = driver.ref_task.family
    assert pathlib.Path(family.__file__) == \
        bench / "reference" / f"{tiny.FAMILY}.py"
    task = driver.spec["task"]
    per = family.train_flops(cell.config["model"], task["local_batch"],
                             task["seq_len"])
    assert driver.work(3)["flops"] == 3 * 6 * per
    arch = program.arch(cell.config, bench)
    assert not arch.tie_embeddings


STAND_INS = [None, "control"] + [("f32", fault) for fault in FLEET.FAULTS]
STAND_IN_IDS = ["sound", "control", *FLEET.FAULTS]


@pytest.mark.parametrize("name,stand_in", [
    (name, s) for name in tiny.FIXTURE_CELLS for s in STAND_INS], ids=[
    f"{prefix}{i}" for prefix in ("", "recount-") for i in STAND_IN_IDS])
def test_family_cell_checked(family_bench, name, stand_in):
    """The fixture families' tiny fleet-train cells: the sound run is
    correct; the control and each fault, in the program's place, fail the
    check."""
    bench, _, benchmark, _ = family_bench
    result = tiny.run_tiny((bench, benchmark), name, stand_in)
    assert result["correct"] == (stand_in is None), result["checks"]
    assert result["metrics"]["fleet_rounds_per_s"]["value"] > 0
