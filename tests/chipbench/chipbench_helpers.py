"""Shared by the chipbench tests: the repo root on sys.path, and a temporary
benchmark root to which a test adds files (never editing one that is there)."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = dict(hidden_size=256, intermediate_size=512, num_attention_heads=2,
            num_key_value_heads=1, head_dim=128, vocab_size=1024)


def copy_root(tmp: str) -> str:
    """BENCHMARK.json and chipbench's data directories, copied."""
    root = os.path.join(str(tmp), "root")
    os.makedirs(os.path.join(root, "chipbench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "jobs", "adapters", "layer_metrics", "reducers"):
        shutil.copytree(os.path.join(ROOT, "chipbench", d),
                        os.path.join(root, "chipbench", d))
    return root


def read(path):
    with open(path) as f:
        return json.load(f)


def write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def add_cell(root, bench, name, config, traffic, like):
    """The way a PR adds a cell: a new entry of ``workloads``, and its name
    appended to the ``workloads`` list of each metric it reports (here: what
    the cell ``like`` reports). Nothing that is there is edited."""
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)


# ---- part 1 of ISSUE 27: what the manifest must hold, as functions of a root,
# so that a root with cells added is held to it too (test_manifest.py on the
# repo's, test_rehearsal.py on a temporary one with six cells)

# the four accepted cells, letter for letter: additions after them pass, an
# edit to one of them fails
ACCEPTED_CELLS = [
    {"name": "mistral-7b.bare", "config": "mistral-7b", "traffic": "bare", "chips": 1,
     "why": "batch 4 x seq 2048 closed loop, fused donated optax step, no Manager: "
            "the ceiling; model, remat and kernel work shows, FT layers bypassed"},
    {"name": "mistral-7b.managed-1g", "config": "mistral-7b", "traffic": "managed-1g",
     "chips": 1,
     "why": "same shapes, one replica group under lighthouse + Manager + "
            "ProcessGroupHost + vote: the bucket pipeline (D2H, pack, unpack, H2D) "
            "is ~96% of the step; wire bypassed (world of one)"},
    {"name": "internlm2-1.8b.managed-1g", "config": "internlm2-1.8b",
     "traffic": "managed-1g", "chips": 1,
     "why": "second configuration through the same Manager and bucket code at "
            "batch 4 x seq 2048: a change tuned on one is measured on the other"},
    {"name": "internlm2-1.8b.kill-rejoin-4g", "config": "internlm2-1.8b",
     "traffic": "kill-rejoin-4g", "chips": 4,
     "why": "4 groups x 1 chip, one scripted SIGKILL: survivors commit on, launcher "
            "restart, HTTP heal into HBM, rejoin; exists only across chips; exercises "
            "ring wire and recovery; correct, HBM and set-up decide"},
]
# the four accepted end-to-end metrics without their ``workloads`` lists (a
# new cell appends its name there)
ACCEPTED_END_TO_END = [
    {"name": "tok_s_chip", "unit": "tokens/s/chip", "better": "higher",
     "bound": 0.1, "source": "host_clock"},
    # the bare cell repeats to 0.001%: its own metric, so that the managed
    # cells' run-to-run noise does not set the bound of the one cell built to
    # show model, remat and kernel work
    {"name": "bare_tok_s_chip", "unit": "tokens/s/chip", "better": "higher",
     "bound": 0.01, "source": "host_clock"},
    {"name": "peak_hbm_gib", "unit": "GiB", "better": "lower", "bound": 0.01,
     "source": "host_clock"},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
]
RATES = {"tok_s_chip", "bare_tok_s_chip"}
# the job kinds whose one event yields no rate: the cell decides on
# ``correct``, HBM and set-up (ISSUE 23 rule 3)
NO_RATE_JOBS = {"kill_rejoin"}


def check_contract(root):
    """The manifest's keys, the accepted cells and metrics unchanged at the
    head of their lists, the shape of every metric entry."""
    from chipbench import manifest

    bench = manifest.load(root)
    assert manifest.problems(root) == []
    assert sorted(bench) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert bench["workloads"][:4] == ACCEPTED_CELLS
    assert [{k: v for k, v in m.items() if k != "workloads"}
            for m in bench["end_to_end"][:4]] == ACCEPTED_END_TO_END
    # all cells report HBM and set-up: no list to be left out of
    assert all("workloads" not in m for m in bench["end_to_end"][2:4])
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert sorted(set(m) - {"workloads"}) == ["better", "bound", "name", "source", "unit"]
    for m in bench["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    return bench


def check_cell(root, name):
    """One cell: every file it names loads; it reports set-up, HBM and a
    per-layer metric; a cell whose job kind yields a rate reports exactly one
    of the two rates (or an end-to-end metric that a later PR brought), and
    the failure cell is told by its job kind, not by its name."""
    import re

    from chipbench import manifest

    c = manifest.Cell(root, manifest.load(root), name)
    assert callable(c.job().run)
    assert callable(c.adapter().config) and callable(c.adapter().register)
    names = {m["name"] for m in c.end_to_end}
    assert {"setup_s", "peak_hbm_gib"} <= names and c.per_layer
    # the failure cell has no deciding time but set-up (ISSUE 23 rule 3):
    # its recovery phases, rejoin.work_s among them, are per-layer
    failure = c.traffic["job"] in NO_RATE_JOBS
    brought = names - {m["name"] for m in ACCEPTED_END_TO_END}
    if failure:
        assert not names & RATES
    else:
        assert len(names & RATES) == 1 or (brought and not names & RATES)
    assert ("rejoin.work_s" in {m["name"] for m in c.per_layer}) == failure
    for m in c.per_layer:
        spec = c.layer_metric(m["name"])
        assert callable(c.reducer(spec["reducer"]).reduce)
        assert m["moves"] in names
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    return c


def check_config_files(root):
    """Every configuration file: each key in ``reduced`` stands in
    ``published`` with another value, and no other key does; the manifest's
    entry lists the same keys."""
    from chipbench import manifest

    for entry in manifest.load(root)["configs"]:
        c = read(os.path.join(root, entry["file"]))
        assert c["name"] == entry["name"], entry["file"]
        assert sorted(c.get("reduced", [])) == sorted(entry["reduced"]) \
            == sorted(c.get("published", {})), entry["file"]
        for k in c.get("reduced", []):
            assert c[k] != c["published"][k], (entry["file"], k)


# ---- the next model_config PR in small: an adapter that is not ``llama``,
# its plain reference and its FLOPs, as new files in a temporary root

TOY_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
            "num_attention_heads": "n_head", "num_key_value_heads": "n_kv_head",
            "head_dim": "d_head", "num_hidden_layers": "n_layer"}

TOY_ADAPTER = '''"""Toy adapter (written by tests/chipbench/chipbench_helpers.py): a dense
decoder whose configuration files use other key names than Hugging Face's,
registered under a name of its own, with its own FLOP count and its own plain
reference beside reference.py."""
import importlib.util
import os

from chipbench.worker import TRAINER

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "toy_reference.py")
_spec = importlib.util.spec_from_file_location("chipbench_toy_reference", _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)
GRAD_LEAVES = ["lm_head", "layers.wo"]


def config(cfg):
    for k in ("hidden_size", "num_experts"):
        if k in cfg:
            raise ValueError(f"adapter 'toy' cannot express key {k!r}")
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["d_model"], n_layers=cfg["n_layer"],
        n_heads=cfg["n_head"], n_kv_heads=cfg["n_kv_head"], ffn_hidden=cfg["d_ff"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]])


def register(cfg):
    from torchft_tpu.models.llama import CONFIGS

    CONFIGS["toy." + cfg["name"]] = config(cfg)
    return TRAINER, ["--config", "toy." + cfg["name"]]


def program():
    from torchft_tpu.models.llama import llama_forward, llama_init, llama_loss

    return llama_init, llama_loss, llama_forward


def num_params(cfg):
    d, q, kv = cfg["d_model"], cfg["n_head"] * cfg["d_head"], cfg["n_kv_head"] * cfg["d_head"]
    return (cfg["n_layer"] * (2 * d * q + 2 * d * kv + 3 * d * cfg["d_ff"] + 2 * d)
            + 2 * cfg["vocab_size"] * d + d)


def train_flops_per_token(cfg, seq):
    return 6.0 * num_params(cfg)  # the toy's own count: no attention term


def _attention(cfg, batch, seq, passes):
    return {"flops": 7.0 * batch * seq, "bytes": 1.0}


KERNEL_COSTS = {"attention": _attention}


def layers_with(cfg, kernel):
    return cfg["n_layer"]
'''

TOY_REFERENCE = '''"""Toy plain reference (written by the tests): the toy's key names mapped
to the equations of chipbench/reference.py; as a script it writes the answers
for the check sample wherever it runs (a test's child runs on the CPU)."""
import json
import sys

sys.path.insert(0, %(repo)r)  # its child finds chipbench like the test does
from chipbench import reference as _plain  # noqa: E402

KEYS = %(keys)r
loss, grad_answers = _plain.loss, _plain.grad_answers


def _hf(cfg):
    return {**cfg, **{hf: cfg[toy] for hf, toy in KEYS.items()}}


def forward(params, tokens, cfg, **kw):
    return _plain.forward(params, tokens, _hf(cfg), **kw)


def check_sample(cfg, sample, seq):
    return _plain.check_sample(cfg, sample, seq)


def answers(params, tokens, cfg, positions, sample, **kw):
    return _plain.answers(params, tokens, _hf(cfg), positions, sample, **kw)


def main(argv):
    import jax
    import numpy as np

    from chipbench import manifest

    with open(argv[0]) as f, open(argv[1]) as g:
        cfg, sample = json.load(f), json.load(g)
    adapter = manifest.adapter_for(argv[0], cfg)
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    params = adapter.program()[0](jax.random.PRNGKey(sample["seed"]), adapter.config(cfg))
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
''' % {"keys": TOY_KEYS, "repo": ROOT}


def add_toy(root, bench, tiny=None):
    """What the next model_config PR brings, as files and entries only: an
    adapter, its plain reference, a configuration that names the adapter, a
    bare-kind cell and a managed-1g cell with their ``workloads`` appends."""
    os.makedirs(os.path.join(root, "chipbench", "adapters"), exist_ok=True)
    with open(os.path.join(root, "chipbench", "adapters", "toy.py"), "w") as f:
        f.write(TOY_ADAPTER)
    with open(os.path.join(root, "chipbench", "toy_reference.py"), "w") as f:
        f.write(TOY_REFERENCE)
    cfg = read(os.path.join(root, "chipbench", "configs", "mistral-7b.json"))
    cfg.update(tiny or {}, name="toy-model", adapter="toy", model_type="toy")
    for hf, toy in TOY_KEYS.items():
        cfg[toy] = cfg.pop(hf)
    cfg["published"], cfg["reduced"] = {"n_layer": 32}, ["n_layer"]
    write(os.path.join(root, "chipbench", "configs", "toy-model.json"), cfg)
    bench["configs"].append({"name": "toy-model", "source": "x", "reduced": ["n_layer"],
                             "file": "chipbench/configs/toy-model.json", "why": "x"})
    add_cell(root, bench, "toy-model.bare", "toy-model", "bare", "mistral-7b.bare")
    add_cell(root, bench, "toy-model.managed-1g", "toy-model", "managed-1g",
             "mistral-7b.managed-1g")
    write(os.path.join(root, "BENCHMARK.json"), bench)
    return cfg


def files_of(root):
    """{path: bytes} of every file under ``root``."""
    return {p: open(p, "rb").read() for d, _, fs in os.walk(root)
            for p in (os.path.join(d, f) for f in fs)}


def only_appended(before: dict, after: dict) -> bool:
    """``after`` is ``before`` with entries appended to its lists and
    nothing else: what BENCHMARK.json takes from a PR that adds a cell."""
    if isinstance(before, dict):
        return isinstance(after, dict) and set(before) == set(after) \
            and all(only_appended(before[k], after[k]) for k in before)
    if isinstance(before, list):
        return isinstance(after, list) and len(after) >= len(before) \
            and all(only_appended(b, a) for b, a in zip(before, after))
    return before == after
