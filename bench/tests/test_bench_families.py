"""Model families found by the configuration's ``model_type``: the llama
family as it was before the lookup, a fixture family added to a copy of
the benchmark as new files only and run as a tiny fleet-train cell on the
CPU, and an unknown family refused by name."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

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


def _parent_flops(config: dict, spec: dict) -> float:
    """Per-client operations as the driver counted them before families."""
    task = spec["task"]
    if "model" in config:
        return FLOPS.train_flops(config["model"], task["local_batch"],
                                 task["seq_len"])
    dims = [task["feature_dim"], *task["hidden"], task["num_classes"]]
    return FLOPS.mlp_train_flops(dims, task["local_batch"])


def test_llama_arch_as_before():
    config = _config("smollm-135m")
    assert "model_type" not in config["model"]
    assert program.arch(config) == _parent_arch(config)


def test_llama_train_flops_is_the_yardsticks():
    model = _config("smollm-135m")["model"]
    assert REF_LLAMA.train_flops(model, 2, 256) == \
        FLOPS.train_flops(model, 2, 256)


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_work_flops_as_before(name):
    cell = run.Cell(name)
    driver = cell.driver(2**31 + 5)
    spec = driver.spec
    topo, sched = spec["topology"], spec["schedule"]
    m = sched.get("participants_per_cell", 0)
    if sched["participation"] == "full" or not 0 < m < topo[
            "clients_per_cell"]:
        m = topo["clients_per_cell"]
    clients = topo["num_cells"] * m
    assert driver.work(7)["flops"] == 7 * clients * _parent_flops(
        cell.config, spec)


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


@pytest.fixture(scope="module")
def family_bench(tmp_path_factory):
    bench, benchmark = tiny.make_bench(tmp_path_factory.mktemp("family"))
    added = tiny.add_family(bench, benchmark)
    return bench, benchmark, added


def test_family_added_as_new_files_only(family_bench):
    bench, _, added = family_bench
    for path in added:
        assert not (BENCH / path.relative_to(bench)).exists(), path
    repo = [p for p in BENCH.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts and "fixtures" not in p.parts]
    assert len(repo) > 40
    for path in repo:
        copy = bench / path.relative_to(BENCH)
        assert copy.read_bytes() == path.read_bytes(), path


def test_family_found_by_model_type(family_bench):
    bench, benchmark, _ = family_bench
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


@pytest.mark.parametrize("stand_in", [None, "control"] + [
    ("f32", fault) for fault in FLEET.FAULTS],
    ids=["sound", "control", *FLEET.FAULTS])
def test_family_cell_checked(family_bench, stand_in):
    """The fixture family's tiny fleet-train cell: the sound run is
    correct; the control and each fault, in the program's place, fail the
    check."""
    bench, benchmark, _ = family_bench
    result = tiny.run_tiny((bench, benchmark), tiny.FAMILY_CELL, stand_in)
    assert result["correct"] == (stand_in is None), result["checks"]
    assert result["metrics"]["fleet_rounds_per_s"]["value"] > 0
