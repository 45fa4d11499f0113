"""The serving MoE dispatch by sorting (ISSUE 35): a call of more than
``SORTED_MIN_TOKENS`` tokens orders its (token, slot) rows by expert and
multiplies each expert's weights by that expert's rows only
(``tpu9.ops.grouped_ffn``), where the one-hot form multiplies every expert
by a capacity of all the call's rows. It is the same algorithm — dropless
top-k — so it equals ``moe_ffn`` at ``capacity_factor = E/k`` under any
routing; which form a call takes follows from its shape, its params and its
mesh, and only the admission group's program changes."""

import functools
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.analysis.graphcheck.passes import walk_eqns
from tpu9.models import moe
from tpu9.models.moe import (SORTED_MIN_TOKENS, MoeConfig, init_moe_layer,
                             moe_ffn, moe_ffn_sorted, takes_held_form,
                             takes_sorted_form)
from tpu9.ops import grouped_ffn as grouped_ops
from tpu9.ops import held_ffn as held_ops

E, DIM, HIDDEN = 8, 128, 384          # three hidden tiles of 128 a step
GROUPED = {
    "kernel": functools.partial(grouped_ops.grouped_ffn_kernel,
                                interpret=True),
    "xla": grouped_ops.grouped_ffn_xla,
}
# what the first feature (held at 4.0 in every token) adds to each expert's
# logit: the routing, whatever else the token holds
ROUTING = {
    "uniform": np.zeros(E),
    "one-expert": np.array([10., 5., 0, 0, 0, 0, 0, 0]),    # top-1: all on 0
    "two-empty": np.array([0, 0, 0, 0, 0, 0, -10., -10.]),
}
# f32: summation order alone. bf16: h is rounded once here and twice in the
# one-hot form (2**-8 relative each), on outputs of order 1
TOLERANCE = {"float32": 1e-5, "bfloat16": 4e-2}


def _layer(dtype, k, routing):
    cfg = MoeConfig(dim=DIM, hidden_dim=HIDDEN, n_experts=E, top_k=k,
                    capacity_factor=E / k, dtype=dtype)
    params = init_moe_layer(jax.random.PRNGKey(0), cfg)
    params["router"] = params["router"].at[0].set(
        jnp.asarray(ROUTING[routing], jnp.float32))
    return cfg, params


def _tokens(n, dtype, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, n, DIM), jnp.float32)
    return x.at[..., 0].set(4.0).astype(dtype)


@pytest.mark.parametrize("grouped", list(GROUPED))
@pytest.mark.parametrize("k", [1, 2], ids=["top1", "top2"])
@pytest.mark.parametrize("n", [257, 512])     # 257 * k: no multiple of a tile
@pytest.mark.parametrize("routing", list(ROUTING))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_sorted_form_equals_one_hot_dropless(dtype, routing, n, k, grouped):
    cfg, params = _layer(dtype, k, routing)
    x = _tokens(n, dtype)
    want, aux = moe_ffn(params, x, cfg, ep_sharded=False)
    assert float(aux["dropped_frac"]) == 0.0
    load = np.asarray(aux["expert_load"])
    if routing == "one-expert":
        assert load[0] == 1.0
    if routing == "two-empty":
        assert load[6] == load[7] == 0.0
    got = moe_ffn_sorted(params, x, cfg, grouped_ffn=GROUPED[grouped])
    assert got.shape == x.shape and got.dtype == x.dtype
    tol = TOLERANCE[jnp.dtype(dtype).name]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("grouped", list(GROUPED))
@pytest.mark.parametrize("routing", ["uniform", "one-expert"])
def test_a_token_does_not_depend_on_the_rest_of_the_call(routing, grouped):
    """Permute the tokens, un-permute the result: a token's row sits in
    another tile of another place, and reads the same."""
    cfg, params = _layer(jnp.float32, 2, routing)
    x = _tokens(300, jnp.float32)
    perm = np.random.default_rng(0).permutation(300)
    run = functools.partial(moe_ffn_sorted, params, cfg=cfg,
                            grouped_ffn=GROUPED[grouped])
    got = np.asarray(run(x[:, perm]))[:, np.argsort(perm)]
    np.testing.assert_allclose(got, np.asarray(run(x)), atol=1e-5, rtol=1e-5)


def test_the_kernel_cuts_long_experts_into_segments_and_skips_empties():
    """Experts without a tile get no grid row, an expert with more tiles
    than a segment holds gets several, and tiles nobody owns are not
    written: the kernel equals the oracle on every owned row."""
    tm = grouped_ops.ROW_TILE
    tiles = jnp.asarray([0, 6, 0, 1, 0, 0, 4, 0], jnp.int32)
    assert int(tiles.max()) > grouped_ops.SEGMENT_TILES
    rows = 13 * tm
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    xs = jax.random.normal(keys[0], (rows, DIM), jnp.float32)
    w = [jax.random.normal(key, shape, jnp.float32) * 0.05 for key, shape in
         zip(keys[1:], [(E, DIM, HIDDEN), (E, DIM, HIDDEN), (E, HIDDEN, DIM)])]
    got = GROUPED["kernel"](xs, tiles, *w)
    want = grouped_ops.grouped_ffn_xla(xs, tiles, *w)
    owned = int(tiles.sum()) * tm
    np.testing.assert_allclose(np.asarray(got)[:owned],
                               np.asarray(want)[:owned], atol=1e-5,
                               rtol=1e-5)
    expert, first, count = grouped_ops._segments(tiles, 13 // 4 + E)
    used = np.asarray(count) > 0
    assert np.asarray(expert)[used].tolist() == [1, 1, 3, 6]
    assert np.asarray(first)[used].tolist() == [0, 4, 6, 7]
    assert np.asarray(count)[used].tolist() == [4, 2, 1, 4]
    assert set(np.asarray(expert)[~used].tolist()) == {6}


# -- the rule -------------------------------------------------------------------

def _bf16_stacks():
    stack = jax.ShapeDtypeStruct((E, DIM, HIDDEN), jnp.bfloat16)
    return {"w_gate": stack, "w_up": stack,
            "w_down": jax.ShapeDtypeStruct((E, HIDDEN, DIM), jnp.bfloat16)}


def test_the_rule_reads_the_calls_shape():
    params = _bf16_stacks()
    assert SORTED_MIN_TOKENS == 256
    assert not takes_sorted_form(params, 32)
    assert not takes_sorted_form(params, 128)
    assert not takes_sorted_form(params, 256)
    assert takes_sorted_form(params, 257)
    assert takes_sorted_form(params, 512)
    f32 = {k: jax.ShapeDtypeStruct(v.shape, jnp.float32)
           for k, v in params.items()}
    assert not takes_sorted_form(f32, 512)


def test_the_decode_steps_rule_reads_the_mask_and_the_calls_shape(
        monkeypatch):
    """ISSUE 50: a call that says which rows are live, of at most
    ``SORTED_MIN_TOKENS`` rows, over bf16 stacks in one device's memory that
    outweigh the kernel's fixed cost a call: Mixtral's 2.8 GB a layer do,
    a test's 2.4 MB do not."""
    params = _bf16_stacks()
    live = jax.ShapeDtypeStruct((32, 1), jnp.bool_)
    assert not takes_held_form(params, 32, live)
    wide = jax.ShapeDtypeStruct((8, 4096, 14336), jnp.bfloat16)
    assert takes_held_form({"w_gate": wide, "w_up": wide, "w_down": wide},
                           32, live)
    monkeypatch.setattr(moe, "HELD_MIN_STACK_BYTES", 0)
    assert takes_held_form(params, 32, live)
    assert takes_held_form(params, 256, live)
    assert not takes_held_form(params, 257, live)
    assert not takes_held_form(params, 32, None)
    assert not takes_held_form(params, 128, None)       # a chunk: no mask
    f32 = {k: jax.ShapeDtypeStruct(v.shape, jnp.float32)
           for k, v in params.items()}
    assert not takes_held_form(f32, 32, live)


def _pallas_calls(jaxpr, name):
    return [e for e in walk_eqns(jaxpr) if e.primitive.name == "pallas_call"
            and name in str(e.params.get("name", ""))]


def _grouped_calls(jaxpr):
    return [e for e in walk_eqns(jaxpr)
            if e.primitive.name.startswith("ragged_dot")
            ] + _pallas_calls(jaxpr, "grouped_ffn")


def _expert_einsums(jaxpr, moe_params):
    """``dot_general`` over a whole expert stack: the one-hot form's."""
    shapes = {tuple(np.shape(w["q"] if isinstance(w, dict) else w))
              for name, w in moe_params.items() if name != "router"}
    return [e for e in walk_eqns(jaxpr) if e.primitive.name == "dot_general"
            and shapes & {tuple(v.aval.shape) for v in e.invars}]


def _tiny_moe_block(case, rows=(1, 512), live=None):
    """A serving ``_mlp_block`` call of 512 tokens (or ``rows``, with the
    mask ``live``) on mixtral-tiny in bf16: plain, with int8 expert
    entries, or on a two-device mesh."""
    from tpu9.models import init_decoder
    from tpu9.models.mixtral import MIXTRAL_PRESETS
    from tpu9.models.transformer import _mlp_block
    from tpu9.ops.quant import quantize_decoder
    cfg = replace(MIXTRAL_PRESETS["mixtral-tiny"], dtype=jnp.bfloat16)
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    mesh = None
    if case == "int8":
        params = quantize_decoder(params)
    if case == "mesh":
        from tpu9.parallel import make_mesh
        mesh = make_mesh(dp=1, fsdp=1, sp=1, tp=2,
                         devices=jax.devices()[:2])
    layer = params["layers"][0]
    x = jnp.zeros((*rows, cfg.dim), cfg.dtype)
    jaxpr = jax.make_jaxpr(lambda la, x: _mlp_block(
        la, x, cfg, serving=True, mesh=mesh, live=live)[0])(layer, x).jaxpr
    return jaxpr, layer["moe"]


@pytest.mark.parametrize("case", ["plain", "int8", "mesh"])
def test_int8_entries_and_a_sharded_stack_keep_the_one_hot_form(case):
    jaxpr, moe_params = _tiny_moe_block(case)
    grouped = _grouped_calls(jaxpr)
    einsums = _expert_einsums(jaxpr, moe_params)
    if case == "plain":
        assert len(grouped) == 3 and not einsums    # ragged_dot, off the TPU
    else:
        assert not grouped and len(einsums) == 3


@pytest.mark.parametrize("case,rows,masked", [
    ("plain", (32, 1), True),       # a decode step: the touched form
    ("mesh", (32, 1), True), ("int8", (32, 1), True),
    ("plain", (32, 1), False),      # no one says which rows are live
    ("plain", (1, 128), False),     # a chunk
    ("plain", (2, 256), True)],     # too many rows for every row an expert
    ids=["step", "mesh", "int8", "no-mask", "chunk", "wide"])
def test_a_decode_step_takes_the_touched_form_and_nothing_else_does(
        monkeypatch, case, rows, masked):
    """ISSUE 50: a plain expert decoder's step, as the chip dispatches it,
    is ONE ``held_ffn`` kernel and no product over a whole stack; a mesh,
    int8 entries, a call without a live mask and the 128-row chunk keep the
    one-hot einsums, the wide call its sorted form."""
    monkeypatch.setattr(held_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "HELD_MIN_STACK_BYTES", 0)     # a test's widths
    live = jnp.ones(rows, bool) if masked else None
    jaxpr, moe_params = _tiny_moe_block(case, rows, live)
    held = _pallas_calls(jaxpr, "held_ffn")
    einsums = _expert_einsums(jaxpr, moe_params)
    if (case, masked) == ("plain", True) and rows == (32, 1):
        assert len(held) == 1 and not einsums
    elif rows == (2, 256):
        assert not held and not einsums and len(_grouped_calls(jaxpr)) == 3
    else:
        assert not held and len(einsums) == 3


def test_a_training_call_keeps_the_one_hot_form_and_its_statistics():
    from tpu9.models import init_decoder
    from tpu9.models.mixtral import MIXTRAL_PRESETS
    from tpu9.models.transformer import _mlp_block
    cfg = replace(MIXTRAL_PRESETS["mixtral-tiny"], dtype=jnp.bfloat16)
    layer = init_decoder(jax.random.PRNGKey(0), cfg)["layers"][0]
    x = jnp.zeros((1, 512, cfg.dim), cfg.dtype)
    _, aux = _mlp_block(layer, x, cfg)
    assert set(aux) == {"balance_loss", "dropped_frac", "expert_load"}
    _, aux = _mlp_block(layer, x, cfg, serving=True)
    assert aux is None
    # a mask alone does not make a call a serving step
    _, aux = _mlp_block(layer, x[:, :32], cfg, live=jnp.ones((1, 32), bool))
    assert set(aux) == {"balance_loss", "dropped_frac", "expert_load"}


@pytest.fixture(scope="module")
def mixtral_jobs():
    """Every serving program of ``mixtral-8x7b-l4`` at the benchmark's
    engine sizes, traced from shapes, the grouped matmul as the chip runs
    it (the kernel)."""
    from benchmark import manifest, serve
    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard.policy import SingleDevicePolicy
    config = manifest.load_config(manifest.load(), "mixtral-8x7b-l4")
    family = manifest.family(config)
    cfg = family.program_config(family.model_sizes(config))
    ecfg = serve.engine_config(config["engine"])
    policy = SingleDevicePolicy()
    graphs = GraphFactory(cfg, ecfg, policy, chunk=ecfg.prefill_chunk)
    st = abstract_state(cfg, ecfg, policy)
    params = abstract_params_for(cfg, False)
    on_tpu = grouped_ops.on_tpu, held_ops.on_tpu
    grouped_ops.on_tpu = held_ops.on_tpu = lambda: True
    try:
        jobs = {key: fn.trace(*args).jaxpr.jaxpr
                for key, fn, args in graphs.lowering_jobs(
                    params, st["kv_cache"], st["pool"], st["scratch"],
                    st["mb"], [ecfg.prefill_chunk], (4,), st["rng"])}
    finally:
        grouped_ops.on_tpu, held_ops.on_tpu = on_tpu
    return cfg, ecfg, params["layers"][0]["moe"], jobs


@pytest.mark.parametrize("program", [
    ("chunk", 128), ("decode", 1), ("decode", 8), ("verify", 4),
    ("chunkgroup", 4)], ids=lambda key: f"{key[0]}{key[1]}")
def test_only_the_group_program_takes_the_sorted_form(mixtral_jobs, program):
    """... and only the decode programs the touched form (ISSUE 50): the
    128-token chunk and the verify window keep the one-hot einsums."""
    cfg, ecfg, moe_params, jobs = mixtral_jobs
    assert (ecfg.max_batch, ecfg.prefill_chunk, ecfg.admit_group_chunks) \
        == (32, 128, 4)
    jaxpr = jobs[program]
    grouped = _grouped_calls(jaxpr)
    held = _pallas_calls(jaxpr, "held_ffn")
    einsums = _expert_einsums(jaxpr, moe_params)
    if program[0] == "chunkgroup":
        assert len(grouped) == cfg.n_layers         # one fused kernel a layer
        assert not einsums and not held             # no [E, C, .] einsum
    elif program[0] == "decode":
        assert len(held) == cfg.n_layers            # the body of the K scan
        assert not einsums and not grouped
    else:
        assert not grouped and not held
        assert len(einsums) == 3 * cfg.n_layers


def test_a_dense_models_programs_do_not_import_the_grouped_matmul(
        monkeypatch):
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving.engine import EngineConfig
    from tpu9.serving.graphs import GraphFactory, abstract_state
    from tpu9.serving.shard.policy import SingleDevicePolicy
    monkeypatch.delitem(sys.modules, "tpu9.ops.grouped_ffn")
    cfg = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.bfloat16)
    ecfg = EngineConfig(max_batch=2, max_seq_len=cfg.max_seq_len,
                        decode_steps=(1,), kv_block_size=128,
                        kv_pool_blocks=8, prefill_chunk=128,
                        admit_group_chunks=4)
    policy = SingleDevicePolicy()
    graphs = GraphFactory(cfg, ecfg, policy, chunk=128)
    st = abstract_state(cfg, ecfg, policy)
    params = jax.eval_shape(lambda: init_decoder(jax.random.PRNGKey(0), cfg))
    keys = [key for key, fn, args in graphs.lowering_jobs(
        params, st["kv_cache"], st["pool"], st["scratch"], st["mb"], [128],
        (), st["rng"]) if fn.trace(*args)]
    assert ("chunkgroup", 4) in keys and ("decode", 1) in keys
    assert "tpu9.ops.grouped_ffn" not in sys.modules


# -- the engine: a plain expert decoder's windows say what was picked ------------

def _serve_tiny_mixtral(dtype, prompts, new=12):
    """Three requests on four lanes of a paged engine over mixtral-tiny,
    dropless: (the engine's stats, the tokens)."""
    import asyncio

    from tpu9.models import init_decoder
    from tpu9.models.mixtral import MIXTRAL_PRESETS
    from tpu9.serving.engine import EngineConfig, InferenceEngine
    tiny = MIXTRAL_PRESETS["mixtral-tiny"]
    cfg = replace(tiny, dtype=dtype,
                  moe_capacity_factor=tiny.n_experts / tiny.moe_top_k)
    engine = InferenceEngine(
        init_decoder(jax.random.PRNGKey(0), cfg), cfg,
        EngineConfig(max_batch=4, max_seq_len=256, prefill_buckets=(32,),
                     decode_steps=(1, 8), kv_block_size=16,
                     kv_pool_blocks=40, prefill_chunk=32,
                     admit_group_chunks=2, temperature=0.0))

    async def go():
        await engine.start()
        outs = await asyncio.gather(*(
            engine.generate(list(p), max_new_tokens=new) for p in prompts))
        await engine.stop()
        return outs
    return cfg, engine.stats, asyncio.run(go())


def test_the_engine_counts_the_experts_a_plain_decoders_steps_touched(
        monkeypatch):
    """bf16 stacks on one device: every decode window returns the chosen
    experts beside its tokens and the engine counts, over the live lanes of
    every step, the experts touched — what ``/health`` carries for the
    benchmark's ``moe_touched_share``. A float32 model keeps the one-hot
    form: no window says, the counters stay 0."""
    monkeypatch.setattr(moe, "HELD_MIN_STACK_BYTES", 0)     # a test's widths
    rng = np.random.default_rng(50)
    prompts = [rng.integers(3, 500, n).tolist() for n in (40, 9, 70)]
    cfg, stats, outs = _serve_tiny_mixtral(jnp.bfloat16, prompts)
    st = stats()
    assert [len(o) for o in outs] == [12, 12, 12]
    assert st["moe_experts_held"] == cfg.n_experts
    assert st["moe_step_layers"] == cfg.n_layers * st["decode_steps"] > 0
    # at most three live lanes x top-2 of four experts: some step leaves an
    # expert untouched, none leaves all
    assert st["moe_step_layers"] <= st["moe_held_touched"] \
        < cfg.n_experts * st["moe_step_layers"]
    assert st["moe_local_picks"] == cfg.moe_top_k * st["moe_token_layers"]
    assert sum(st["moe_held_pick_hist"]) == st["moe_local_picks"]
    assert st["graph_compiles_post_warmup"] == 0
    _, stats, outs = _serve_tiny_mixtral(jnp.float32, prompts)
    st = stats()
    assert [len(o) for o in outs] == [12, 12, 12]
    assert st["moe_experts_held"] == cfg.n_experts
    assert st["moe_step_layers"] == st["moe_held_touched"] == 0
