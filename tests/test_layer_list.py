"""What a decoder's layers are is said once (ISSUE 64): ``DecoderConfig.layers``
is built from whichever spelling the constructor was given, the questions a
program, the state's format and the engine ask are named properties with
their rule in ``models/transformer.py``, and nothing else in ``tpu9/`` reads
how the pattern was spelled."""

import ast
import dataclasses
import pathlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from tpu9.models import init_decoder, kvstate
from tpu9.models.transformer import DecoderConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent
SPELLING = ("layer_group", "layer_pattern", "ffn_pattern")
BASE = dict(vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, head_dim=16,
            hidden_dim=128, max_seq_len=512, dtype=jnp.float32)
LATENT = dict(BASE, n_kv_heads=4, mla_latent=64, mla_nope=32, mla_rope=16,
              mla_v=32)
SHARE = dict(n_experts=8, moe_top_k=2, moe_hidden_dim=48, moe_routed=8,
             moe_score="sigmoid", moe_select_bias=True)
HALVES = {"M": ("ssm", "none"), "*": ("full", "none"), "E": ("none", "experts")}
NEMOTRON = "MEM*EME"
LFM2 = ("conv", "conv", "full", "conv", "conv", "conv")
SSM = dict(ssm_heads=4, ssm_head_dim=32, ssm_state=32, ssm_conv=4)
# one configuration a spelling, as the cells of the benchmark spell theirs
# (ling-reason, kimi-docs, granite-chat, nemotron-agents, lfm2-sessions,
# mixtral-chat), and the two other decoders whose stream is float32
CONFIGS = {
    "rule": DecoderConfig(**LATENT, n_layers=6, layer_group=3, kda_conv=4,
                          kda_gate_bound=-5.0, moe_dense_layers=1, **SHARE),
    "rule-of-one": DecoderConfig(**LATENT, n_layers=3, layer_group=1,
                                 moe_dense_layers=1, **SHARE),
    "list": DecoderConfig(**BASE, n_layers=5, tie_embeddings=True,
                          layer_pattern=("ssm", "ssm", "full", "ssm", "ssm"),
                          rope=False, residual_mult=0.22, **SSM),
    "two-lists": DecoderConfig(
        **BASE, n_layers=len(NEMOTRON), act="relu2", rope=False,
        layer_pattern=tuple(HALVES[c][0] for c in NEMOTRON),
        ffn_pattern=tuple(HALVES[c][1] for c in NEMOTRON), moe_gated=False,
        moe_latent_dim=32, **dict(SHARE, n_experts=4, moe_held_first=4),
        **SSM),
    "list-conv": DecoderConfig(**BASE, n_layers=6, tie_embeddings=True,
                               layer_pattern=LFM2, conv_taps=3, qk_norm=True,
                               moe_dense_layers=2, **SHARE),
    "none": DecoderConfig(**BASE, n_layers=4, n_experts=8),
    "looped": DecoderConfig(**BASE, n_layers=2, loop_steps=3,
                            sandwich_norm=True, exit_gate=True),
    "window": DecoderConfig(**BASE, n_layers=2, attn_window=64, attn_chunk=4),
}
LAYERS = {
    "rule": (("kda", "dense"), ("kda", "experts"), ("mla", "experts"),
             ("kda", "experts"), ("kda", "experts"), ("mla", "experts")),
    "rule-of-one": (("mla", "dense"),) + (("mla", "experts"),) * 2,
    "list": (("ssm", "dense"),) * 2 + (("full", "dense"),)
    + (("ssm", "dense"),) * 2,
    "two-lists": tuple(HALVES[c] for c in NEMOTRON),
    "list-conv": (("conv", "dense"),) * 2 + (("full", "experts"),)
    + (("conv", "experts"),) * 3,
    "none": (("full", "experts"),) * 4,
    "looped": (("full", "dense"),) * 2,
    "window": (("full", "dense"),) * 2,
}


@pytest.mark.parametrize("name", CONFIGS)
def test_the_layers_are_what_the_spelling_says(name):
    """``layers`` is the rule's / the lists' statement, and every question
    answers as the PARENT's expression over the spelling did (each written
    out here as it stood at 1d4b90b)."""
    cfg = CONFIGS[name]
    assert cfg.layers == LAYERS[name]
    assert [cfg.layer_kind(l) for l in range(cfg.n_layers)] \
        == list(cfg.layers)
    kinds = [kind for kind, _ in cfg.layers]
    # transformer.py:284 (lane_state), :299 (kv_layers), :267 (kv_row)
    stated = bool(cfg.layer_group or cfg.layer_pattern)
    assert cfg.lane_state == tuple(
        k for k in ("kda", "ssm", "conv") if stated and k in kinds)
    assert cfg.kv_layers == (
        kinds.count("mla") if cfg.layer_group else
        kinds.count("full") if cfg.layer_pattern else
        cfg.n_layers * cfg.loop_steps)
    # kvstate.py:258 (heads_per_row, the list's condition with it)
    narrow = cfg.head_dim < 128 and 128 % cfg.head_dim == 0
    pack = min(128 // cfg.head_dim, cfg.n_kv_heads) \
        if cfg.layer_pattern and narrow else 1
    assert cfg.kv_pack == (pack if cfg.n_kv_heads % pack == 0 else 1)
    assert cfg.kv_row == (
        ((1, cfg.mla_latent), (1, cfg.mla_rope)) if cfg.layer_group else
        ((cfg.n_kv_heads // cfg.kv_pack, cfg.head_dim * cfg.kv_pack),) * 2)
    # kvstate.py:115, engine.py:244 and the others: ``bool(layer_group)``
    assert cfg.latent_rows == bool(cfg.layer_group)
    # transformer.py:890, graphs.py:228 and the others
    assert cfg.uniform == (not (cfg.layer_group or cfg.lane_state)) \
        == (not stated)
    # transformer.py:886 and ``_looped_passes``
    assert cfg.wide_stream == bool(cfg.attn_window or cfg.looped
                                   or cfg.layer_pattern)
    assert cfg.pattern_label == (f"layer_group={cfg.layer_group}"
                                 if cfg.layer_group else "layer_pattern")


@pytest.mark.parametrize("name", CONFIGS)
def test_the_state_and_the_trees_follow_the_layers(name):
    """The lanes' arrays are those of ``lane_state``'s kinds, a plane a
    layer of the kind; a seeded layer has a norm for each half it has, its
    kind's tree and its feed-forward part, and nothing of another kind."""
    cfg = CONFIGS[name]
    shapes = kvstate.lane_shapes(cfg, 3)
    assert set(shapes) == {array for kind in cfg.lane_state
                           for array in kvstate.LANE_KINDS[kind]}
    for kind in cfg.lane_state:
        for array in kvstate.LANE_KINDS[kind]:
            assert shapes[array][0][:2] == (len(cfg.layers_of(kind)), 3)
    assert kvstate.pool_shapes(cfg, 4, 16)["k"][0][0] == cfg.kv_layers
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    trees = {"kda": {"kda"}, "mla": {"mla"}, "ssm": {"ssm"},
             "conv": {"conv"}, "full": {"wq", "wk", "wv", "wo"}, "none": set()}
    for layer, (attention, ffn) in zip(params["layers"], cfg.layers):
        assert ("attn_norm" in layer) == (attention != "none")
        assert ("mlp_norm" in layer) == (ffn != "none")
        assert ("moe" in layer) == (ffn == "experts")
        assert ("w_up" in layer) == (ffn == "dense")
        for kind, names in trees.items():
            assert all((n in layer) == (kind == attention) for n in names)


def test_layers_is_derived_and_not_a_field():
    cfg = CONFIGS["rule"]
    assert "layers" not in {f.name for f in dataclasses.fields(cfg)}
    twin = replace(cfg)
    assert twin is not cfg and twin == cfg and hash(twin) == hash(cfg)
    assert twin.layers == cfg.layers
    # another depth is another statement, made when the instance is
    longer = replace(cfg, n_layers=9)
    assert len(longer.layers) == 9 and longer.layers[8] == ("mla", "experts")
    assert longer != cfg and len(cfg.layers) == 6
    relisted = replace(CONFIGS["list"], n_layers=2,
                       layer_pattern=("full", "ssm"))
    assert relisted.layers == (("full", "dense"), ("ssm", "dense"))
    # frozen like the fields: nobody restates a live instance's layers
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.layers = ()


PARENT_FIELDS = [
    ("vocab_size", 32000), ("dim", 4096), ("n_layers", 32), ("n_heads", 32),
    ("n_kv_heads", 8), ("head_dim", 128), ("hidden_dim", 14336),
    ("norm_eps", 1e-5), ("rope_theta", 500000.0), ("max_seq_len", 8192),
    ("act", "silu"), ("norm_offset", 0.0), ("embed_scale", False),
    ("logit_softcap", 0.0), ("tie_embeddings", False), ("n_experts", 0),
    ("moe_top_k", 2), ("moe_capacity_factor", 1.25), ("loop_steps", 1),
    ("sandwich_norm", False), ("exit_gate", False), ("exit_threshold", 1.0),
    ("attn_window", 0), ("attn_chunk", 0), ("layer_group", 0),
    ("mla_latent", 0), ("mla_nope", 0), ("mla_rope", 0), ("mla_v", 0),
    ("mla_q_latent", 0), ("mla_out_gate", True), ("mla_mscale", 1.0),
    ("rope_yarn", ()), ("kda_conv", 0), ("kda_gate_bound", 0.0),
    ("moe_dense_layers", 0), ("moe_hidden_dim", 0), ("moe_routed", 0),
    ("moe_held_first", 0), ("moe_shared_dim", 0), ("moe_score", "softmax"),
    ("moe_select_bias", False), ("moe_groups", 0), ("moe_top_groups", 0),
    ("moe_renormalise", True), ("moe_gate_scale", 1.0),
    ("layer_pattern", ()), ("ssm_heads", 0), ("ssm_head_dim", 0),
    ("ssm_state", 0), ("ssm_groups", 1), ("ssm_conv", 0),
    ("ssm_norm_groups", 1), ("ffn_pattern", ()), ("moe_gated", True),
    ("moe_latent_dim", 0), ("rope", True), ("attn_scale", 0.0),
    ("embed_mult", 1.0), ("residual_mult", 1.0), ("logit_div", 1.0),
    ("conv_taps", 0), ("qk_norm", False), ("dtype", jnp.bfloat16)]


def test_the_constructor_is_the_parents():
    """The 64 fields — names, order, defaults — as they stood at 1d4b90b,
    found as the benchmark's harness finds them: ``benchmark/families/
    looped.py`` reads the annotated assignments in the body of ``class
    DecoderConfig`` from the SOURCE of ``tpu9/models/transformer.py``, and
    every family builds the config with these keywords. What is derived
    (``layers``, the questions) is no field."""
    tree = ast.parse((ROOT / "tpu9/models/transformer.py").read_text())
    (body,) = [node.body for node in tree.body
               if isinstance(node, ast.ClassDef)
               and node.name == "DecoderConfig"]
    in_source = [stmt.target.id for stmt in body
                 if isinstance(stmt, ast.AnnAssign)]
    assert in_source == [name for name, _ in PARENT_FIELDS]
    assert [(f.name, f.default) for f in dataclasses.fields(DecoderConfig)] \
        == PARENT_FIELDS
    assert len(PARENT_FIELDS) == 64


# who may read how the pattern was spelled: the class (its fields' one reader
# is ``_layer_list``) and the refusals its ``__post_init__`` calls
SPELLING_READERS = {
    "tpu9/models/transformer.py": {"DecoderConfig", "_layer_list"},
    "tpu9/models/ssm.py": {"refuse_unbuilt_list", "_refuse_unbuilt_conv_list",
                           "_refuse_unbuilt_halves"},
    "tpu9/models/hybrid.py": {"refuse_unbuilt_pattern"},
}


def _spelling_reads(path: pathlib.Path) -> list:
    """``(top-level definition, line, attribute)`` of every attribute read
    of a spelling field in the file."""
    found = []
    for top in ast.parse(path.read_text()).body:
        for node in ast.walk(top):
            if isinstance(node, ast.Attribute) and node.attr in SPELLING:
                found.append((getattr(top, "name", "<module>"), node.lineno,
                              node.attr))
    return found


def test_nothing_else_asks_how_the_pattern_was_spelled():
    """By ``ast`` over ``tpu9/``: 64 attribute reads of ``layer_group`` /
    ``layer_pattern`` / ``ffn_pattern`` at 1d4b90b, 44 of them outside
    ``transformer.py``; now none in ``tpu9/serving/``, none in
    ``kvstate.py``, and in ``tpu9/models/`` only the class's own and the
    refusals'. The sites found are printed."""
    strays, kept = [], 0
    for path in sorted((ROOT / "tpu9").rglob("*.py")):
        name = path.relative_to(ROOT).as_posix()
        for top, line, attr in _spelling_reads(path):
            print(f"{name}:{line} {top} reads {attr}")
            if top in SPELLING_READERS.get(name, ()):
                kept += 1
            else:
                strays.append((name, line, top, attr))
    assert not strays
    assert 0 < kept < 30
