"""Which of ``ops/kda_passes.py``'s two kernel pairs moves what a KDA cell's
check reads, and by how much: the check's arithmetic under replay, with the
SIGNED distance of the gradient's norm from the reference's (the check holds
its absolute value to a limit), for the program as it is and for four other
builds of the mixer's element-wise passes, each alone and with the faults
named:

``tree``: the module as it is (both kernel pairs where the shape tiles);
``qkg_ref``: ``kda_qkg`` in its ``jax.numpy`` form, the gate's kernels on;
``gate_ref``: ``kda_gate`` in its ``jax.numpy`` form (in ``out_block``'s
rematerialised blocks where the recipe says so), ``kda_qkg``'s kernels on;
``both_ref``: both ``jax.numpy`` forms, the arithmetic of PR 66's parent;
``gate_rounded``: the gate's kernels with the ``jax.numpy`` form's roundings
inside (after the norm, after the weight, the gate, after the gate, and
every cotangent of a value so rounded), put into the module from here.

The faults are ``ling_check_faults.py``'s or ``solar_check_faults.py``'s, by
the cell's name. PERF.md section 6, PR 66, has the readings.

    chiprun -- python3 benchmarks/kda_passes_builds.py <workload> <build,...> [fault ...]

One JSON line a (build, variant); exits 2 without a TPU.
"""

import contextlib
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

BUILDS = ("tree", "qkg_ref", "gate_ref", "both_ref", "gate_rounded")


def _script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _rounded_gate_kernels():
    """``kda_gate``'s two kernel bodies with ``gate_reference``'s roundings
    and the roundings of its pullback as XLA writes it."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import kda_passes as kp

    f32 = jnp.float32

    def r(x, like):  # the digits of the rows' dtype, in float32
        return x.astype(like.dtype).astype(f32)

    def forward(eps, dk, o_ref, l_ref, w_ref, out_ref):
        per_head = l_ref.shape != o_ref.shape

        def body(rows, cols, h):
            o = o_ref[rows, cols].astype(f32)
            logits = l_ref[rows, h:h + 1] if per_head else l_ref[rows, cols]
            rms = jax.lax.rsqrt(kp._sum(o * o) / dk + eps)
            nw = r(r(o * rms, o_ref) * w_ref[:, cols], o_ref)
            out_ref[rows, cols] = (nw * r(jax.nn.sigmoid(logits), o_ref)).astype(out_ref.dtype)

        kp._walk(*o_ref.shape, dk, body)

    def backward(eps, dk, o_ref, l_ref, w_ref, dout_ref, do_ref, dl_ref, dw_ref):
        kp._first_step(dw_ref)
        per_head = l_ref.shape != o_ref.shape

        def body(rows, cols, h):
            o, dout, w = o_ref[rows, cols].astype(f32), dout_ref[rows, cols].astype(f32), \
                w_ref[:, cols]
            logits = l_ref[rows, h:h + 1] if per_head else l_ref[rows, cols]
            rms, s = jax.lax.rsqrt(kp._sum(o * o) / dk + eps), jax.nn.sigmoid(logits)
            n = r(o * rms, o_ref)
            nw, dnw = r(n * w, o_ref), r(dout * r(s, o_ref), o_ref)
            dn = r(dnw * w, o_ref)
            do_ref[rows, cols] = ((dn - o * (rms * rms * kp._sum(dn * o) / dk)) * rms).astype(
                do_ref.dtype)
            at_gate = kp._sum(dout * nw) if per_head else dout * nw
            where = slice(h, h + 1) if per_head else cols
            dl_ref[rows, where] = r(at_gate, o_ref) * (s * (1.0 - s))
            dw_ref[:, cols] += jnp.sum(dnw * n, axis=0, keepdims=True)

        kp._walk(*o_ref.shape, dk, body)

    return forward, backward


@contextlib.contextmanager
def build(name):
    """A context in which ``kda_mixer`` runs the build ``name``."""
    from torchft_tpu.models import kda as mixer
    from torchft_tpu.ops import kda_passes as kp

    was = {(m, k): getattr(m, k) for m, k in (
        (mixer, "kda_qkg"), (mixer, "kda_gate"), (mixer, "tiles"),
        (kp, "_gate_fwd_kernel"), (kp, "_gate_bwd_kernel"))}
    if name in ("qkg_ref", "both_ref"):
        mixer.kda_qkg = kp.qkg_reference
    if name in ("gate_ref", "both_ref"):
        mixer.kda_gate, mixer.tiles = kp.gate_reference, lambda o, dk: False
    if name == "gate_rounded":
        kp._gate_fwd_kernel, kp._gate_bwd_kernel = _rounded_gate_kernels()
    try:
        yield
    finally:
        for (m, k), v in was.items():
            setattr(m, k, v)


def main(argv):
    workload, builds, fault_names = argv[0], argv[1].split(","), argv[2:]
    if set(builds) - set(BUILDS):
        sys.exit(f"builds are {', '.join(BUILDS)}")
    faults = _script("solar_check_faults" if workload.startswith("solar")
                     else "ling_check_faults")
    cell = manifest.Cell(ROOT, manifest.load(ROOT), workload)
    job, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = job.check_sample_of(cell, adapter)
    # a child computes the reference's answers before this process takes the chip
    ref = job._reference_answers(cell, adapter, sample, os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if jax.devices()[0].platform != "tpu":
        return 2
    limits, pc = cell.traffic["check"]["tolerances"], adapter.config(cfg)

    def show(name, variant):
        jax.clear_caches()
        try:
            got = job.system_answers(adapter, cfg, sample, seq, routing=ref["routing"])
            out = job.compare(got, ref, limits)
            out["grad_norm_signed"] = float(got["grad_norm"]) / float(ref["grad_norm"]) - 1.0
            out["loss_signed"] = float(got["loss"]) - float(ref["loss"])
        except Exception as e:  # a build that does not fit says so and the rest go on
            out = {"error": repr(e)[:400]}
        print(json.dumps({"build": name, "variant": variant, **out}), flush=True)

    for name in builds:
        with build(name):
            show(name, "program")
            for fault in fault_names:
                with faults.fault(fault, pc):
                    show(name, fault)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
