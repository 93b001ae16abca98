"""MetricLogger: jsonl sink + wandb run-id persistence for resume.

wandb is not installed on test hosts; these tests stub the module to verify
the resume contract (reference launch.py:59-68: a relaunched run must reuse
the id persisted in rundir/wandb_id.txt) without the dependency.
"""

import importlib.util
import json
import os
import types

import pytest

import midgpt_tpu.training.metrics as metrics
from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPTConfig


def _config(rundir):
    return ExperimentConfig(
        rundir=str(rundir),
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
        warmup_steps=1,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        beta2=0.95,
        weight_decay=1e-4,
        eval_interval=5,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=False,
        mesh=MeshConfig(),
        model_config=GPTConfig(
            block_size=8, vocab_size=16, n_layer=1, n_head=1, n_embd=8
        ),
    )


class _FakeRun:
    def __init__(self, id):
        self.id = id
        self.logged = []

    def log(self, m, step=None):
        self.logged.append((step, m))

    def finish(self):
        pass


def _fake_wandb(created):
    fake = types.SimpleNamespace()
    fake.util = types.SimpleNamespace(generate_id=lambda: "generated123")

    def init(project=None, id=None, resume=None, config=None):
        run = _FakeRun(id)
        created.append(run)
        return run

    fake.init = init
    return fake


def test_jsonl_always_written(tmp_path):
    logger = metrics.MetricLogger(_config(tmp_path), use_wandb=False)
    logger.log(3, {"loss": 1.5})
    logger.close()
    rec = json.loads(open(tmp_path / "metrics.jsonl").read().splitlines()[0])
    assert rec["step"] == 3 and rec["loss"] == 1.5


def test_wandb_id_persisted_and_reused(tmp_path, monkeypatch):
    created = []
    monkeypatch.setattr(metrics, "_wandb", _fake_wandb(created))

    # first launch: generates an id and persists it
    logger = metrics.MetricLogger(_config(tmp_path))
    logger.close()
    id_file = tmp_path / "wandb_id.txt"
    assert id_file.read_text().strip() == "generated123"
    assert created[0].id == "generated123"

    # relaunch (resume): must reuse the persisted id, not generate a new one
    monkeypatch.setattr(
        metrics.MetricLogger, "_persistent_run_id",
        metrics.MetricLogger._persistent_run_id,
    )
    id_file.write_text("previous-run-id")
    logger2 = metrics.MetricLogger(_config(tmp_path))
    logger2.close()
    assert created[1].id == "previous-run-id"


def test_explicit_resume_id_wins(tmp_path, monkeypatch):
    created = []
    monkeypatch.setattr(metrics, "_wandb", _fake_wandb(created))
    logger = metrics.MetricLogger(_config(tmp_path), resume_id="explicit-id")
    logger.close()
    assert created[0].id == "explicit-id"


# ----------------------------------------------------------------------
# one source of truth for speed: the program's FLOP count and peaks against
# the benchmark's own (benchmarks/ is read by path, never imported as a package)
# ----------------------------------------------------------------------

BENCHMARKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")


def _arithmetic():
    spec = importlib.util.spec_from_file_location("arithmetic", os.path.join(BENCHMARKS, "arithmetic.py"))
    arith = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arith)
    return arith


@pytest.mark.parametrize("name", ["midgpt_124m", "midgpt_xl_1p5b"])
def test_flops_per_token_agrees_with_the_yardstick(name):
    """benchmarks/arithmetic.py keeps its own copy of the dense FLOP count so
    that no PR can move a utilization by editing the program; at both
    benchmark configurations the copy and the program give one number."""
    with open(os.path.join(BENCHMARKS, "configs", f"{name}.json")) as f:
        model = json.load(f)["model"]
    mc = GPTConfig(**model)
    arith = _arithmetic()
    assert arith.flops_per_token(model) == metrics.flops_per_token(mc)
    assert arith.flops_per_token(model, 256) == metrics.flops_per_token(mc, 256)


def test_peak_tables_agree():
    """Every device kind the benchmark has a peak for, the train loop's MFU
    divides by the same one (substring-keyed there, exact here)."""
    with open(os.path.join(BENCHMARKS, "peaks.json")) as f:
        table = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
    assert table
    for kind, peaks in table.items():
        device = types.SimpleNamespace(device_kind=kind, platform="tpu")
        assert metrics.device_peak_flops(device) == peaks["bf16_flops_per_s"], kind


def test_unknown_device_kind_has_no_assumed_peak():
    """A device_kind missing from the peaks table is an error naming the
    device, never a default peak; the v5e the chip tool hands out is in it."""
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert metrics.device_peak_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(device_kind="TPU v99x", platform="tpu")
    with pytest.raises(ValueError, match="TPU v99x"):
        metrics.device_peak_flops(unknown)


def _benchmark_module(*path):
    spec = importlib.util.spec_from_file_location(path[-1][:-3], os.path.join(BENCHMARKS, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# (case, op names, what a chip's line looks like as (name index, start, duration) in ns,
#  in flight, exposed)
_GRAD_REDUCE_TRACES = [
    ("synchronous_reduce_scatter_by_the_primitives_name",
     ["fusion.1", "reduce_scatter.93", "all-gather.2"],
     [(0, 0, 100), (1, 100, 50), (2, 150, 30), (0, 180, 20)], 50, 50),
    ("async_permute_pair_hidden_but_for_its_ends",
     ["collective-permute-start.1", "fusion.7", "collective-permute-done.1"],
     [(0, 0, 5), (1, 5, 90), (2, 95, 10)], 105, 15),
    ("two_pairs_in_flight_together_and_a_gap_between_ops",
     ["collective-permute-start.1", "collective-permute-start.2", "fusion.7",
      "collective-permute-done.1", "collective-permute-done.2", "while.3"],
     [(5, 0, 200), (0, 0, 5), (1, 5, 5), (2, 10, 50), (2, 80, 20), (3, 100, 10), (4, 110, 10)],
     120, 50),
    ("ppermute_by_the_primitives_name_beside_a_weight_gather",
     ["ppermute.4", "all-gather-start.1", "all-gather-done.1", "reduce-scatter.9"],
     [(1, 0, 10), (0, 10, 40), (2, 50, 10), (3, 60, 7)], 47, 47),
    ("no_gradient_collective", ["fusion.1", "all-reduce.2"], [(0, 0, 10), (1, 10, 5)], 0, 0),
]


@pytest.mark.parametrize("names, ops, in_flight, exposed", [c[1:] for c in _GRAD_REDUCE_TRACES],
                         ids=[c[0] for c in _GRAD_REDUCE_TRACES])
def test_grad_reduce_reader_on_small_traces(names, ops, in_flight, exposed):
    """benchmarks/metrics/fsdp_grad_reduce.py: a gradient-reduction
    collective is found by the jax primitive's name and by the compiler's,
    is in flight for its own duration (synchronous) or from its start op's
    beginning to its done op's end (a pair), and is EXPOSED where no other
    leaf op runs on the chip: a loop wrapper is no op, a weight all-gather
    is one. Per step and chip; a program with none reports nothing."""
    reduce = _benchmark_module("reduce.py")
    reader = _benchmark_module("metrics", "fsdp_grad_reduce.py")
    ops = sorted(([n, s, d] for n, s, d in ops), key=lambda o: (o[1], -o[2]))
    assert reader.exposed_ns(names, ops, reduce) == (in_flight, exposed)
    run = {
        "kind": "train", "chips": 4, "counters": {"traced_steps": 2}, "log": lambda _: None,
        "load": lambda name: reduce,
        "trace_summary": {"trace": {"names": names}, "n_devices": 2,
                          "devices": [{"ops": ops}, {"ops": ops}]},
    }
    got = reader.read(run)
    if in_flight == 0:
        assert got is None
    else:
        assert list(got) == ["fsdp.grad_reduce_exposed_ms_per_step"]
        assert got["fsdp.grad_reduce_exposed_ms_per_step"] == pytest.approx(exposed / 1e6 / 2)
    assert reader.read({**run, "chips": 1}) is None
