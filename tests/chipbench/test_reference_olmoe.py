"""OLMoE's block against its plain float32 reference at tiny widths on the
CPU, and the ``bare_routed`` check (decisions apart from arithmetic, and the
router alone) with the lower-precision controls each part has to catch — the comparison the
``olmoe-1b-7b.bare-routed`` cell makes at the published widths on the chip."""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

bare = manifest.load_module(ROOT, "jobs", "bare")
routed = manifest.load_module(ROOT, "jobs", "bare_routed")
olmoe = manifest.load_module(ROOT, "adapters", "olmoe")
reference = olmoe.reference
CHECK = read(f"{ROOT}/chipbench/traffic/bare-routed.json")["check"]
TOL = CHECK["tolerances"]
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8,
          "grad_leaves": olmoe.GRAD_LEAVES}
SEQ = 128
# tiny widths, the architecture kept: many small experts, several a token,
# gates not renormalised, QK-norm, MHA
TINY = dict(hidden_size=128, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=4, vocab_size=512, num_hidden_layers=2,
            num_experts=16, num_experts_per_tok=4)


def _tiny(dtype):
    cfg = read(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json")
    cfg.update(TINY)
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _reference(cfg, **dots):
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = olmoe.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), olmoe.config(cfg))
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    return _reference(_tiny("float32"))


def _crushed(x, w):
    """A product whose operands keep 4 mantissa bits: what fp8 would do."""
    def crush(a):
        m, e = jnp.frexp(a)
        return jnp.ldexp(jnp.round(m * 16) / 16, e)
    return jnp.matmul(crush(x), crush(w))


def _bf16(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(jnp.float32)


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: the same experts
    for every token (so the reference's masked dense sum equals the
    program's sort, grouped products and unsort), the same QK-norm, gates and
    load-balancing loss. 1e-5 class: f32 rounding through two layers, sums in
    another order."""
    cfg = _tiny("float32")
    system = routed.system_answers(olmoe, cfg, SAMPLE, SEQ)
    a = routed.decisions(system["routing"], ref32, {"max_share": 0.0, "max_margin": 0.0})
    assert a["ok"] and a["differ_pairs"] == 0, a
    c = routed.router_precision(
        routed.router_answers(olmoe, cfg, SAMPLE, ref32["router_in"]), ref32, CHECK["router"])
    assert c["ok"] and c["differ_pairs"] == 0 and c["prob_rel"] < 1e-5, c
    got = bare.compare(system, ref32, TOL)
    assert sorted(got) == ["grad_norm_rel", "grad_rel.layers.router",
                           "grad_rel.layers.w_down", "grad_rel.layers.wq",
                           "logits_rel", "loss_abs", "ok"]
    assert all(v < 2e-5 for k, v in got.items() if k != "ok"), got
    # the auxiliary loss alone, from the program's own counters
    _, loss_, _ = olmoe.program()
    pc = olmoe.config(cfg)
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    params = olmoe.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), pc)
    _, stats = loss_(params, tokens, tokens, pc, with_stats=True)
    assert float(stats["aux_loss"]) == pytest.approx(ref32["aux_loss"], rel=1e-5)
    assert 4.0 <= ref32["aux_loss"] < 5.5  # k at even load, more when skewed


@pytest.fixture(scope="module")
def ref_bf16_weights():
    return _reference(_tiny("bfloat16"))


def test_bf16_under_replay_is_inside_bares_tolerances(ref_bf16_weights):
    """The chip cell's comparison: the program in bf16 replaying the
    reference's routing is inside ``bare.json``'s tolerances (B), and where
    its own choices differ the reference had a near-tie (A)."""
    cfg, ref = _tiny("bfloat16"), ref_bf16_weights
    got = routed.routed_check(olmoe, cfg, SAMPLE, SEQ, ref, CHECK)
    assert got["arithmetic"]["ok"], got
    assert got["router"]["ok"] and got["router"]["prob_rel"] < 1e-5, got  # float32 on the CPU
    assert got["arithmetic"]["logits_rel"] > 1e-3  # bf16 is visible: not vacuous
    assert got["decisions"]["differ_max_margin"] <= CHECK["routing"]["max_margin"], got
    assert got["decisions"]["differ_share"] <= 0.2, got  # tiny widths: more near-ties
    assert set(got["free"]) >= {"logits_rel", "decisions"}


def test_the_tolerances_are_bares():
    assert CHECK["tolerances"] == read(f"{ROOT}/chipbench/traffic/bare.json")["check"]["tolerances"]
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare", "bare-routed")]
    for key in ("metric", "warmup_steps", "min_steps", "trace_steps"):
        assert both[0][key] == both[1][key]
    assert {k: v for k, v in both[1]["check"]["sample"].items()} == both[0]["check"]["sample"]


def test_fp8_like_expert_products_fail_the_arithmetic(ref32):
    """Expert products on operands of 4 mantissa bits, replaying the
    reference's experts: part B refuses them."""
    low = _reference(_tiny("float32"), expert_dot=_crushed)
    got = bare.compare(low, ref32, TOL)
    assert not got["ok"] and got["grad_rel.layers.w_down"] > TOL["grad_leaf_rel"], got


def test_fp8_like_arithmetic_fails_the_decisions(ref32):
    """Every product on operands of 4 mantissa bits sends tokens elsewhere
    where the reference had no near-tie: part A refuses it at the written
    limits. A router product in bf16 alone it cannot tell from a float32
    one (a handful of pairs, every one a near-tie: the router's input carries
    the rounding of everything before it, PERF.md section 6, PR 28): that is
    part C's."""
    low = _reference(_tiny("float32"), dot=_crushed, router_dot=_crushed)
    got = routed.decisions(low["routing"], ref32, CHECK["routing"])
    assert not got["ok"] and got["differ_max_margin"] > CHECK["routing"]["max_margin"], got
    bf16 = _reference(_tiny("float32"), router_dot=_bf16)
    got = routed.decisions(bf16["routing"], ref32, CHECK["routing"])
    assert got["ok"] and 0 < got["differ_pairs"] <= 5, got


def _bf16_accumulator(x, w):
    """A product whose every multiply-add is rounded to bf16: what a bf16
    accumulator would do (x [T,D] or [e,T,H], w [e,D,H] or [e,H,D])."""
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)

    def add(k, acc):
        xk = jax.lax.dynamic_index_in_dim(xb, k, axis=xb.ndim - 1)
        wk = jax.lax.dynamic_index_in_dim(wb, k, axis=wb.ndim - 2)
        return (acc + xk * wk).astype(jnp.bfloat16)

    zero = jnp.zeros(jnp.matmul(x, w).shape, jnp.bfloat16)
    return jax.lax.fori_loop(0, x.shape[-1], add, zero).astype(jnp.float32)


def test_a_bf16_accumulator_in_the_expert_products_fails_the_arithmetic(ref32):
    """bf16 operands with float32 accumulation are the program's own
    arithmetic and pass; the same products accumulated in bf16 fail part B
    already over the tiny widths' 128 and 64 terms (the published widths
    sum 2,048 and 1,024). On the chip the kernel's accumulator cannot be
    swapped, and its coarsest imitation (8 partial products of 256 terms
    summed in bf16) rounds 8 times, not 2,048, and reads as the program
    does (PERF.md section 6, PR 28): this is where a true one is shown."""
    def bf16_operands(x, w):
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    fine = bare.compare(_reference(_tiny("float32"), expert_dot=bf16_operands), ref32, TOL)
    assert fine["ok"], fine
    low = bare.compare(_reference(_tiny("float32"), expert_dot=_bf16_accumulator), ref32, TOL)
    assert not low["ok"] and low["logits_rel"] > TOL["logits_rel"], low


def _routers_on(router_in, cfg, product):
    """Seeded routers before the reference's router inputs, the product done
    by ``product``: what part C is given."""
    params = olmoe.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), olmoe.config(cfg))
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.stack([
        product(x, w) for x, w in zip(jnp.asarray(router_in), params["layers"]["router"])]), -1)
    top_p, top_i = jax.lax.top_k(probs, k + 1)
    return {"routing": np.array(top_i[..., :k]), "p_kth": np.asarray(top_p[..., k - 1]),
            "p_next": np.asarray(top_p[..., k])}


def test_a_bf16_router_fails_the_router_alone_and_another_summation_order_does_not(ref32):
    """Part C, on the reference's own router inputs: a router product in one
    bf16 pass (what a TPU does with float32 operands unless told otherwise)
    moves the probabilities by some 1e-3 and is refused at the written
    limit; a float32 product summed in four parts, in another order, is
    not. A disagreement is allowed only at a tie to that precision."""
    cfg = _tiny("float32")
    limit = CHECK["router"]
    low = routed.router_precision(_routers_on(ref32["router_in"], cfg, _bf16), ref32, limit)
    assert not low["ok"] and low["prob_rel"] > 10 * limit["max_prob_rel"], low

    def in_parts(x, w):
        return sum(x[:, i::4] @ w[i::4] for i in range(4))

    same = routed.router_precision(_routers_on(ref32["router_in"], cfg, in_parts), ref32, limit)
    assert same["ok"] and same["prob_rel"] < 1e-5, same
    # the same experts reported with one choice swapped where the margin is wide
    forced = _routers_on(ref32["router_in"], cfg, jnp.matmul)
    margin = (ref32["p_kth"] - ref32["p_next"]) / ref32["p_kth"]
    layer, token = np.unravel_index(np.argmax(margin), margin.shape)
    forced["routing"][layer, token, -1] = next(
        e for e in range(TINY["num_experts"]) if e not in forced["routing"][layer, token])
    got = routed.router_precision(forced, ref32, limit)
    assert got["differ_pairs"] == 1 and not got["ok"], got
    forced["routing"] = (ref32["routing"] + 1) % TINY["num_experts"]  # a router gone wrong
    assert not routed.router_precision(forced, ref32, limit)["ok"]


def test_a_disagreement_with_a_wide_margin_fails_the_decisions(ref32):
    """One token sent elsewhere where the reference's k-th choice stood well
    clear of its next: not a near-tie, so (A) fails whatever the share."""
    margin = (ref32["p_kth"] - ref32["p_next"]) / ref32["p_kth"]
    layer, token = np.unravel_index(np.argmax(margin), margin.shape)
    assert margin[layer, token] > CHECK["routing"]["max_margin"]
    forced = ref32["routing"].copy()
    unused = next(e for e in range(TINY["num_experts"]) if e not in forced[layer, token])
    forced[layer, token, -1] = unused
    got = routed.decisions(forced, ref32, CHECK["routing"])
    assert got["differ_pairs"] == 1 and not got["ok"]
    assert routed.decisions(ref32["routing"], ref32, CHECK["routing"])["ok"]
    # the same one pair at a near-tie passes
    layer, token = np.unravel_index(np.argmin(margin), margin.shape)
    forced = ref32["routing"].copy()
    forced[layer, token, -1] = next(
        e for e in range(TINY["num_experts"]) if e not in forced[layer, token])
    assert routed.decisions(forced, ref32, CHECK["routing"])["ok"]


def test_the_timed_loop_is_bares():
    """``bare_routed.run`` is ``bare.run`` but for the statement that makes
    the check, and one after the trace is read that hands out the compiled
    step's scopes: what is timed, and how, is the same yardstick."""
    rx = re.compile(r"    verdict = .*?\n(?=    marks\[\"check_s\"\])", re.S)
    scopes = re.compile(r"        obs\[\"scopes\"\] = .*?\n(?=    peak = )", re.S)
    a, b = inspect.getsource(bare.run), inspect.getsource(routed.run)
    assert len(rx.findall(a)) == len(rx.findall(b)) == 1
    assert len(scopes.findall(b)) == 1 and not scopes.findall(a)
    assert rx.sub("", a) == scopes.sub("", rx.sub("", b))
    assert "routed_check(" in rx.findall(b)[0]


def test_scopes_are_read_from_the_compiled_text():
    """``scopes_of`` on a compiled program of this JAX: the instructions a
    trace would name carry the program's ``jax.named_scope`` path, which the
    ``device_scope`` reducer matches; a job that hands out no map leaves the
    metric out."""
    def f(x):
        with jax.named_scope("moe/route"):
            y = jnp.sin(x) @ x
        return jnp.cos(y).sum()

    text = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile().as_text()
    names = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", text, re.M)
    got = routed.scopes_of(text, names + ["not-there"])
    assert "not-there" not in got and any("moe/route" in v for v in got.values())
    inside = [n for n, v in got.items() if "moe/route" in v]
    reducer = manifest.load_module(ROOT, "reducers", "device_scope")
    obs = {"trace": {"ops": {**{n: 2.0 for n in names}, "gmm.3": 4.0}, "chips_traced": 1},
           "steps_in_window": 2, "scopes": got}
    block = reducer.reduce(obs, None, scope="moe/(route|dispatch)", also=r"^t?gmm(\.\d+)?$")
    assert block == len(inside) + 2.0
    assert reducer.reduce(obs, None, scope="moe/(route|dispatch)",
                          without=r"^t?gmm(\.\d+)?$") == len(inside)
    assert reducer.reduce({**obs, "scopes": None}, None, scope="moe/route") is None
    assert reducer.reduce(obs, None, scope="no/such/scope") is None


@pytest.mark.parametrize("kind", sorted(
    f[:-3] for f in __import__("os").listdir(f"{ROOT}/chipbench/jobs") if f.endswith(".py")))
def test_no_job_kind_names_a_model(kind):
    text = open(f"{ROOT}/chipbench/jobs/{kind}.py").read()
    code = "\n".join(ln for ln in text.split('"""')[2:] if ln)  # past the docstring
    assert "llama" not in code.lower() and "torchft_tpu.models" not in code, kind
    assert "moe" not in code.lower(), kind
