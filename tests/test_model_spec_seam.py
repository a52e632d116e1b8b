"""The seam every new configuration crosses: ``model_spec`` takes the
model's options from ``TransformerConfig``'s own fields, coerces each by
its declared type, and the configuration is checked where it is built.
Nothing here is traced or compiled."""

import dataclasses
import glob
import inspect
import json
import os

import pytest

from benchmark.lib.runner import params_string
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.models.spec import load_model_spec

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = sorted(glob.glob(
    os.path.join(HERE, "..", "benchmark", "configs", "*.json")))

# field -> (as the command line writes it, what the configuration must
# hold, the other keys without which that value is refused).  A new
# field wants a row: that is the whole of the test's upkeep.
AS_TYPED = {
    "vocab_size": ("96", 96, ""),
    "dim": ("48", 48, ""),
    "num_heads": ("4", 4, ""),
    "num_layers": ("3.0", 3, ""),
    "mlp_ratio": ("2", 2, ""),
    "dtype": ("float32", "float32", ""),
    "tied_embeddings": ("false", False, ""),
    "embed_scale": ("1", 1.0, ""),
    "ffn_dim": ("96.0", 96, ""),
    "norm_eps": ("1e-5", 1e-5, ""),
    "qk_norm": ("head", "head", ""),
    "post_norms": ("True", True, ""),
    "attn_gate": ("true", True, ""),
    "embed_multiplier": ("45.25", 45.25, ""),
    "rope_theta": ("500000", 500000.0, ""),
    "rope_kinds": ("w", "w", ""),
    "head_dim": ("16", 16, ""),
    "kv_latent_rank": ("32", 32,
                       "qk_nope_dim=16;qk_rope_dim=8;v_head_dim=8"),
    "qk_nope_dim": ("16", 16,
                    "kv_latent_rank=32;qk_rope_dim=8;v_head_dim=8"),
    "qk_rope_dim": ("8", 8,
                    "kv_latent_rank=32;qk_nope_dim=16;v_head_dim=8"),
    "v_head_dim": ("8", 8,
                   "kv_latent_rank=32;qk_nope_dim=16;qk_rope_dim=8"),
    "layer_pattern": ("cawa", "cawa", "window=8"),
    "dense_layers": ("1", 1, "moe_experts=4;dense_ffn_dim=64"),
    "dense_ffn_dim": ("64", 64, ""),
    "conv_kernel": ("4", 4, ""),
    "moe_experts": ("4", 4, ""),
    "moe_top_k": ("1", 1, ""),
    "moe_norm_topk": ("false", False, ""),
    "moe_aux_weight": ("0", 0.0, ""),
    "moe_router": ("sigmoid_bias", "sigmoid_bias", ""),
    "moe_route_scale": ("2.448", 2.448, ""),
    "moe_route_before_op": ("true", True, ""),
    "ffn_activation": ("relu", "relu", ""),
    "moe_experts_held": ("2", 2, "moe_experts=4"),
    "moe_share_index": ("1", 1, "moe_experts=4;moe_experts_held=2"),
    "moe_shared_experts": ("2", 2, ""),
    "remat": ("true", True, ""),
    "attention_impl": ("ulysses", "ulysses", ""),
    "window": ("8", 8, ""),
    "num_kv_heads": ("2", 2, ""),
    "pre_norms": ("false", False, "post_norms=true"),
    "delta_key_dim": ("16", 16, ""),
    "delta_value_dim": ("32", 32, ""),
    "delta_neg_eigval": ("true", True, ""),
    "delta_kind": ("kda", "kda", ""),
    "delta_rank": ("16", 16, ""),
    "head_shares": ("2", 2, ""),
    "q_latent_rank": ("24", 24, "kv_latent_rank=32;qk_nope_dim=16;qk_rope_dim=8;v_head_dim=8"),
    "rope_scaling": ("64,4096,32,1", "64,4096,32,1", "kv_latent_rank=32;qk_nope_dim=16;qk_rope_dim=8;v_head_dim=8"),
    "hyper_streams": ("4", 4, ""),
    "hyper_sinkhorn_iters": ("5", 5, ""),
    "mtp_modules": ("1", 1, ""),
    "mtp_weight": ("0.3", 0.3, ""),
    "scan_periods": ("false", False, ""),
    "delta_gate_floor": ("-5", -5.0, "delta_kind=kda"),
    "moe_groups": ("4", 4, "moe_experts=8;moe_top_groups=2;"
                   "moe_router=sigmoid_bias"),
    "moe_top_groups": ("2", 2, "moe_experts=8;moe_groups=4;"
                       "moe_router=sigmoid_bias"),
    "ffn_limits": ("4,0", "4,0", "moe_experts=4;num_layers=2;"
                   "scan_periods=false;dense_layers=0;layer_pattern=aa"),
    "shared_limits": ("0,7", "0,7", "moe_experts=4;num_layers=2;"
                      "scan_periods=false;layer_pattern=aa"),
    "ssm_heads": ("4", 4, ""),
    "ssm_head_dim": ("16", 16, ""),
    "ssm_state": ("32", 32, ""),
    "ssm_groups": ("2", 2, ""),
    "conv_bias": ("true", True, "num_layers=2;layer_pattern=ma;ssm_heads=2;"
                  "ssm_head_dim=8;ssm_state=8"),
    "mixer_ffn": ("false", False, "num_layers=2;layer_pattern=ae"),
    "ut_steps": ("4", 4, ""),
    "ut_entropy_weight": ("0.05", 0.05, "ut_steps=2"),
}
FIELDS = [field for field in dataclasses.fields(tfm.TransformerConfig)
          if field.name != "max_seq_len"]


@pytest.mark.parametrize("field", FIELDS, ids=lambda field: field.name)
def test_every_field_is_a_key_of_model_params_at_its_own_type(field):
    written, want, others = AS_TYPED[field.name]
    cfg = load_model_spec(
        "transformer", "%s=%s;%s" % (field.name, written, others)).config
    got = getattr(cfg, field.name)
    assert got == want and type(got) is type(want)
    assert want != field.default        # the key was read, not passed over
    assert isinstance(got, field.type)  # bool | str: either


def test_seq_len_is_max_seq_lens_key_and_its_only_one():
    assert load_model_spec("transformer",
                           "seq_len=64").config.max_seq_len == 64
    with pytest.raises(TypeError, match="max_seq_len"):
        load_model_spec("transformer", "max_seq_len=64")


def test_a_key_that_is_no_field_is_refused_by_name():
    with pytest.raises(TypeError, match="moe_expert_count"):
        load_model_spec("transformer", "dim=32;moe_expert_count=4")


def test_the_signature_names_what_is_not_the_models_and_nothing_else():
    names = list(inspect.signature(tfm.model_spec).parameters)
    assert names == ["seq_len", "learning_rate", "warmup_steps", "mesh",
                     "pipeline_microbatches", "xent_chunk", "model_params"]


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_a_benchmark_configuration_builds_the_model_it_always_did(path):
    """``cli.model_params`` as ``benchmark/lib/runner.py`` joins them,
    against the ``TransformerConfig`` the same file built at PR 42's
    commit (``benchmark_model_configs.json``)."""
    with open(path) as fh:
        cli = json.load(fh)["cli"]
    with open(os.path.join(HERE, "benchmark_model_configs.json")) as fh:
        was = json.load(fh)[os.path.basename(path)]
    cfg = load_model_spec(cli["model_zoo"],
                          params_string(cli["model_params"])).config
    assert cfg == tfm.TransformerConfig(**was)
    # and at the parent's types, but for the one value that went in as
    # the parser left it (0 for 0.0) where it is a float now
    assert all(type(getattr(cfg, name)) is type(value)
               for name, value in was.items() if name != "moe_aux_weight")


def test_every_benchmark_configuration_has_its_literal():
    with open(os.path.join(HERE, "benchmark_model_configs.json")) as fh:
        assert sorted(json.load(fh)) == [os.path.basename(path)
                                         for path in CONFIGS]
    assert len(CONFIGS) >= 6


@pytest.mark.parametrize("word", ["attn", "dots"])
def test_remats_two_strings_are_refused_by_name(word):
    for build in (lambda: load_model_spec("transformer", "remat=" + word),
                  lambda: tfm.TransformerConfig(remat=word)):
        with pytest.raises(ValueError, match="remat") as refusal:
            build()
        assert repr(word) in str(refusal.value)
        assert "remat=true keeps the flash" in str(refusal.value)


@pytest.mark.parametrize("bad,match", [
    (dict(num_heads=4, num_kv_heads=3), "num_kv_heads"),
    (dict(kv_latent_rank=32), "latent attention needs"),
    (dict(moe_experts=8, moe_experts_held=3), "moe_experts_held"),
    (dict(layer_pattern="awxw", window=8), "letters"),
    (dict(layer_pattern="awww"), "window"),
    (dict(rope_kinds="ac"), "rope_kinds"),
    (dict(layer_pattern="acca", dense_layers=1), "dense_layers"),
    (dict(attn_gate=True, layer_pattern="caca"), "w_attn_gate"),
    (dict(ffn_activation="gelu"), "ffn_activation"),
    (dict(attention_impl="rings"), "attention_impl"),
    (dict(moe_router="softmin"), "moe_router"),
    (dict(qk_norm="yes"), "qk_norm"),
    (dict(post_norms="false"), "post_norms"),
    (dict(delta_kind="ssm"), "delta_kind"),
    (dict(num_layers=2, layer_pattern="ad", delta_key_dim=16,
          delta_value_dim=16, delta_gate_floor=-5.0), "delta_gate_floor"),
    (dict(num_layers=2, layer_pattern="ad", delta_key_dim=16,
          delta_value_dim=16, delta_kind="kda", delta_rank=-1),
     "delta_rank"),
    (dict(num_layers=2, layer_pattern="ad", delta_key_dim=16,
          delta_value_dim=16, delta_kind="kda", delta_gate_floor=5.0),
     "floor <= 0"),
    (dict(moe_experts=8, moe_groups=4, moe_top_groups=2), "sigmoid_bias"),
    (dict(moe_experts=8, moe_groups=3, moe_top_groups=2,
          moe_router="sigmoid_bias"), "moe_groups=3"),
    (dict(moe_experts=8, moe_groups=4, moe_top_groups=5,
          moe_router="sigmoid_bias"), "moe_top_groups=5"),
    (dict(moe_experts=8, moe_groups=4, moe_top_groups=1, moe_top_k=3,
          moe_router="sigmoid_bias"), "moe_top_k"),
    (dict(moe_experts=4, num_layers=2, ffn_limits="4"), "ffn_limits='4'"),
    (dict(num_layers=2, ffn_limits="4,4"), "ffn_limits"),
    (dict(moe_experts=4, num_layers=2, shared_limits="0,x"), "L0,L1"),
    (dict(moe_experts=4, num_layers=2, ffn_limits="0,4"),
     "scan_periods=false"),
    (dict(moe_experts=4, num_layers=2, dense_layers=1, dense_ffn_dim=8,
          ffn_limits="4,4"), "dense layer's limit"),
    (dict(attn_gate="value"), "attn_gate"),
    (dict(attn_gate=True, kv_latent_rank=32, qk_nope_dim=16, qk_rope_dim=8,
          v_head_dim=8), "takes head alone"),
    (dict(num_layers=2, layer_pattern="ad", delta_key_dim=16,
          delta_value_dim=16, delta_rank=8), "delta_kind=gdn"),
])
def test_a_configuration_built_directly_is_refused_where_it_is_built(
        bad, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(**bad)


def test_no_property_of_the_configuration_refuses_anything():
    for name, member in vars(tfm.TransformerConfig).items():
        if isinstance(member, property):
            assert "raise" not in inspect.getsource(member.fget), name


KDA = dict(num_layers=4, layer_pattern="addd", rope_kinds="w", num_heads=2,
           num_kv_heads=1, head_dim=32, dim=64, delta_key_dim=32,
           delta_value_dim=32, delta_kind="kda", delta_rank=16, conv_kernel=4,
           moe_experts=8, moe_experts_held=2, vocab_size=64, max_seq_len=64)


@pytest.mark.parametrize("what,call", [
    ("decode_step", lambda cfg: tfm.decode_step(None, cfg, None, 0, None)),
    ("prefill", lambda cfg: tfm.prefill(None, cfg, None, 8)),
    ("forward_pipelined", lambda cfg: tfm.forward_pipelined(
        None, None, cfg, None, 2)),
    ("a model-parallel mesh", tfm.param_specs),
])
def test_a_kda_stack_is_refused_in_the_one_place_by_its_fields(what, call):
    """Decoding, the pipelined forward and a mesh refuse the stack
    through ``_refuse``'s rows, naming the delta layer's kind beside
    the pattern (and, a mesh, the expert share)."""
    cfg = tfm.TransformerConfig(**KDA)
    with pytest.raises(NotImplementedError) as refusal:
        call(cfg)
    said = str(refusal.value)
    assert said.startswith(what + " does not run a stack whose layers "
                           "differ (layer_pattern='addd', dense_layers=0, "
                           "delta_kind=kda, mixer_ffn=True)")
    assert "gdn or kda" in said
    assert ("moe_experts_held=2" in said) == (what == "a model-parallel mesh")


SSM = dict(num_layers=3, layer_pattern="mae", mixer_ffn=False, rope_kinds="w",
           num_heads=2, num_kv_heads=1, head_dim=32, dim=64, ssm_heads=4,
           ssm_head_dim=16, ssm_state=16, ssm_groups=2, conv_kernel=4,
           conv_bias=True, ffn_activation="relu2", moe_experts=8,
           moe_experts_held=2, vocab_size=64, max_seq_len=64)


@pytest.mark.parametrize("what,call", [
    ("decode_step", lambda cfg: tfm.decode_step(None, cfg, None, 0, None)),
    ("prefill", lambda cfg: tfm.prefill(None, cfg, None, 8)),
    ("forward_pipelined", lambda cfg: tfm.forward_pipelined(
        None, None, cfg, None, 2)),
    ("a model-parallel mesh", tfm.param_specs),
])
def test_a_stack_of_single_sublayers_is_refused_by_its_fields(what, call):
    """Decoding, the pipelined forward and a mesh refuse the Mamba-2
    mixer, the layers of one sublayer and the MLP of two matrices
    through ``_refuse``'s rows, each by its name and its field."""
    cfg = tfm.TransformerConfig(**SSM)
    with pytest.raises(NotImplementedError) as refusal:
        call(cfg)
    said = str(refusal.value)
    assert said.startswith(what + " does not run a stack whose layers "
                           "differ (layer_pattern='mae', dense_layers=0, "
                           "delta_kind=gdn, mixer_ffn=False)")
    assert "a Mamba-2 layer (m) a state [ssm_heads, ssm_head_dim" in said
    assert "a layer of one sublayer (e, or any layer under" in said
    assert (what + " does not run an MLP of two matrices (ffn_activation="
            "relu2: no w_gate, no ws_gate)") in said
    assert ("moe_experts_held=2" in said) == (what == "a model-parallel mesh")


@pytest.mark.parametrize("more, match", [
    (dict(attn_gate=True), "is not held to attn_gate"),
    (dict(mtp_modules=1), "is not held to attn_gate"),
    (dict(ssm_groups=3), "ssm_groups that divide the heads"),
    (dict(layer_pattern="maa"), "mixer_ffn=false: a layer with an operator"),
    (dict(ffn_limits="0,0,4"), "has no gate product"),
])
def test_what_a_stack_of_single_sublayers_cannot_be_is_refused(more, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(**dict(SSM, **more))

