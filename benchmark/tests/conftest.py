"""Tests of the benchmark itself: ``python -m pytest benchmark/tests -q``.
They run on the CPU, with four virtual devices for the data-parallel path
(set before JAX is imported)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import copy  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402

# -- a throw-away extension of the benchmark: what a later PR would add,
# -- at a size a CPU runs. New files in a root of their own and new entries
# -- of BENCHMARK.json; nothing that is there is edited.

TINY_GPT = {
    "source": "throw-away", "family": "transformer_lm", "n_layer": 2,
    "n_embd": 64, "n_head": 2, "n_inner": 128, "n_positions": 128,
    "vocab_size": 300, "eos_token_id": 299, "reduced": [],
    "assumed": {"embedding_rows": 384},
    "training": {
        "compute_dtype": "bfloat16", "param_dtype": "float32",
        "attention": "pallas_flash", "head": "fused_chunked",
        "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                      "weight_decay": 0.01},
        "allreduce_grad_dtype": "bfloat16"},
}
TINY_RESNET = {
    "source": "throw-away", "family": "resnet", "stage_sizes": [1, 1],
    "num_filters": 8, "bottleneck_expansion": 4, "image_size": 32,
    "image_channels": 3, "num_classes": 10, "stem": "standard",
    "reduced": [], "assumed": {"last_bn_scale": [0.02, 0.05],
                               "bn_momentum": 0.9},
    # float32 compute: eight 32x32 images through BatchNorm in bf16 are
    # too noisy to hold to the reference at this toy size
    "training": {
        "compute_dtype": "float32", "param_dtype": "float32",
        "optimizer": {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9},
        "allreduce_grad_dtype": "bfloat16"},
}
TINY_MIX = {
    "what": "throw-away", "loop": "train", "feed": {"depth": 2},
    "warmup_steps": 2,
    "samples": {"tokens": {"pool_batches": 4, "doc_len_median": 40,
                           "doc_len_sigma": 1.0, "zipf_exponent": 1.0},
                "images": {"pool_batches": 2}},
}
LM_JOB = {"per_chip_batch": 2, "remat": "dots", "head_chunks": 2}
#: four new cells, so that the benchmark has eight and a second one may
#: take four chips (a quarter of the cells may); a pair of configuration
#: and mix appears once, so the four-chip cell has a mix of its own
TINY_CELLS = {
    "tiny-lm": {"config": "tiny-gpt", "traffic": "tiny", "chips": 1,
                "why": "x", "job": LM_JOB},
    "tiny-lm-dp4": {"config": "tiny-gpt", "traffic": "tiny-dp4", "chips": 4,
                    "why": "x", "job": {**LM_JOB, "remat": "none"}},
    "tiny-resnet": {"config": "tiny-resnet", "traffic": "tiny", "chips": 1,
                    "why": "x",
                    "job": {"per_chip_batch": 16, "remat": "none"}},
    "tiny-resnet-b8": {"config": "tiny-resnet", "traffic": "tiny-dp4",
                       "chips": 1, "why": "x",
                       "job": {"per_chip_batch": 8, "remat": "none"}},
}
NEW_READER = '''"""A throw-away per-layer metric: steps the loop completed."""
LAYER = "train step"
UNIT = "steps"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "step_ms"


def read(ctx):
    return float(ctx["loop"]["steps"])
'''


@pytest.fixture(scope="session")
def added(tmp_path_factory):
    """``(roots, benchmark)``: a root with new files only beside the
    benchmark's own, and the committed ``BENCHMARK.json`` with new entries
    only."""
    import spec

    root = tmp_path_factory.mktemp("added")
    files = {"configs/tiny-gpt.json": TINY_GPT,
             "configs/tiny-resnet.json": TINY_RESNET,
             "traffic/tiny.json": TINY_MIX,
             "traffic/tiny-dp4.json": TINY_MIX}
    files.update({f"workloads/{name}.json": cell
                  for name, cell in TINY_CELLS.items()})
    for rel, body in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    (root / "layer_metrics").mkdir()
    (root / "layer_metrics" / "steps_done.py").write_text(NEW_READER)

    old = spec.load_benchmark()
    new = copy.deepcopy(old)
    for name in ("tiny-gpt", "tiny-resnet"):
        new["configs"].append({
            "name": name, "source": "throw-away",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "x"})
    for name, cell in TINY_CELLS.items():
        new["workloads"].append(
            {"name": name, **{k: cell[k] for k in
                              ("config", "traffic", "chips", "why")}})
    # a metric that only some cells report lists the new cells of its kind
    lm_cells = [n for n, c in TINY_CELLS.items() if c["config"] == "tiny-gpt"]
    image_cells = [n for n in TINY_CELLS if n not in lm_cells]
    for m in new["end_to_end"] + new["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + (
                image_cells if m["name"] == "images_per_s" else
                ["tiny-lm-dp4"] if m["name"].startswith("allreduce") else
                lm_cells)
    new["per_layer"].append({
        "name": "steps_done", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "step_ms"})
    # only additions: every old entry is still there, letter for letter
    # (a metric's list of cells is the one thing an addition extends)
    for group in ("workloads", "configs", "end_to_end", "per_layer"):
        assert [{k: v for k, v in e.items() if k != "workloads"}
                for e in new[group][:len(old[group])]] == \
            [{k: v for k, v in e.items() if k != "workloads"}
             for e in old[group]]
    return spec.Roots((str(root),)), new
