"""What depends on a model's architecture is chosen by its configuration
file: the plain reference (``reference.module``) and the published keys its
widths are checked against (``program.published``). A new family is then
new files, with no edit to the harness."""
import json
import os
import sys

import pytest

from bench import harness as H
from bench.conftest import TINY_CONFIG, TINY_LIMIT, write_tiny_root

SEED = 2 ** 31 + 5151
STUB_GAP = 0.03125

# A reference that is one new file: its best logit at each position lies
# STUB_GAP above the served token's wherever the stub can see that token
# (every position but the last of a sequence), and level elsewhere.
STUB = f'''
from dataclasses import dataclass

import numpy as np

CALLS = []


@dataclass(frozen=True)
class Dims:
    vocab: int

    @classmethod
    def from_config(cls, c):
        CALLS.append("from_config")
        return cls(vocab=c["vocab_size"])


def make_weights(m, key):
    CALLS.append("make_weights")
    return {{}}


def logits_at(m, w, seq, at, quant=None):
    CALLS.append("logits_at")
    out = np.zeros((len(at), m.vocab), np.float32)
    for i, a in enumerate(at):
        if a + 1 < len(seq):
            out[i, (int(seq[a + 1]) + 1) % m.vocab] = {STUB_GAP!r}
    return out
'''


def _tiny(tmp_path, name, **reference):
    config = dict(TINY_CONFIG, reference=dict(TINY_CONFIG["reference"],
                                              **reference))
    return write_tiny_root(str(tmp_path / name), config)


def _run(root, **kw):
    return H.run_cell(root, "tiny.tiny", SEED, 1.0, False,
                      require_accelerator=False, **kw)


def test_no_module_and_gqa_load_the_same_reference(tmp_path):
    for root in (_tiny(tmp_path, "implicit"),
                 _tiny(tmp_path, "named", module="gqa")):
        ref = H.load_cell(root, "tiny.tiny").reference
        assert ref.__name__ == "bench_reference_gqa"
        assert ref.__file__ == os.path.join(root, "bench", "reference",
                                            "gqa.py")


def test_a_stub_reference_is_the_one_called(tmp_path):
    root = _tiny(tmp_path, "stub", module="stub")
    with open(os.path.join(root, "bench", "reference", "stub.py"), "w") as f:
        f.write(STUB)
    out = _run(root)
    stub = sys.modules["bench_reference_stub"]
    assert stub.CALLS[:2] == ["from_config", "make_weights"]
    assert set(stub.CALLS[2:]) == {"logits_at"}
    assert out["_log"]["reference_module"] == "stub"
    assert out["checks"]["max_logit_gap"] == {"value": STUB_GAP,
                                              "limit": TINY_LIMIT}
    assert out["correct"] is True


@pytest.mark.parametrize("fault", ["absent", "path", "Dims", "make_weights",
                                   "logits_at", "from_config"])
def test_a_missing_or_incomplete_reference_is_refused(tmp_path, capsys,
                                                      fault):
    module = {"absent": "nosuch", "path": "../reference/gqa"}.get(fault,
                                                                  "part")
    root = _tiny(tmp_path, "bad", module=module)
    src = STUB
    if fault in ("Dims", "make_weights", "logits_at"):
        src = src.replace(f"def {fault}(", f"def other_{fault}(").replace(
            f"class {fault}:", f"class Other{fault}:")
    elif fault == "from_config":
        src = src.replace("def from_config(", "def other(")
    with open(os.path.join(root, "bench", "reference", "part.py"), "w") as f:
        f.write(src)
    with pytest.raises(H.Refused) as e:
        H.load_cell(root, "tiny.tiny")
    assert e.value.code == 2
    said = capsys.readouterr().err
    assert module in said
    if fault not in ("absent", "path"):
        assert fault in said


# ------------------------------------------------------- published keys
DEEPSEEK_V2 = {
    # published config.json keys, one of the configuration's files as a
    # DeepSeek-V2 cell would hold them, with the depth cut to 2 layers
    "name": "deepseek-v2", "model_type": "deepseek_v2",
    "hidden_size": 5120, "intermediate_size": 12288,
    "moe_intermediate_size": 1536, "num_hidden_layers": 2,
    "num_attention_heads": 128, "num_key_value_heads": 128,
    "n_routed_experts": 160, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "first_k_dense_replace": 1,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
    "qk_nope_head_dim": 128, "v_head_dim": 128, "vocab_size": 102400,
    "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "program": {"registry": "deepseek-v2-236b",
                "overrides": {"num_layers": 2, "norm_eps": 1e-06}},
}
DEEPSEEK_V2_MAP = {
    "d_model": "hidden_size", "num_layers": "num_hidden_layers",
    "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
    "vocab_size": "vocab_size", "d_ff": "intermediate_size",
    "num_experts": "n_routed_experts",
    "num_shared_experts": "n_shared_experts",
    "experts_per_token": "num_experts_per_tok",
    "moe_d_ff": "moe_intermediate_size", "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank", "qk_rope_head_dim": "qk_rope_head_dim",
    "qk_nope_head_dim": "qk_nope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
    "tie_embeddings": "tie_word_embeddings", "dtype": "torch_dtype",
}


def _mapped(config, published, **overrides):
    program = dict(config["program"], published=published)
    program["overrides"] = dict(program["overrides"], **overrides)
    return dict(config, program=program)


def test_a_published_map_checks_deepseek_v2():
    # the registry's d_ff is the expert width, 1536; the published dense
    # width is 12288, and no map can leave it out
    with pytest.raises(ValueError, match="'d_ff': \\(1536, 12288\\)"):
        H.program_config(_mapped(DEEPSEEK_V2, DEEPSEEK_V2_MAP))
    cfg = H.program_config(_mapped(DEEPSEEK_V2, DEEPSEEK_V2_MAP, d_ff=12288))
    assert (cfg.num_layers, cfg.num_experts, cfg.moe_d_ff, cfg.d_ff) == \
        (2, 160, 1536, 12288)


@pytest.mark.parametrize("change,named", [
    ({"d_ff": None}, "published width 'intermediate_size'"),
    ({"kv_lora_rank": None}, "published width 'kv_lora_rank'"),
    ({"vocab_size": None}, "'vocab_size'"),
    ({"dtype": None}, "'dtype'"),
    ({"head_dim": "head_dim"}, "'head_dim'"),
    ({"expert_width": "moe_intermediate_size"}, "'expert_width'"),
    ({"q_lora_rank": "kv_lora_rank", "kv_lora_rank": "q_lora_rank"},
     "'q_lora_rank': (1536, 512)"),
], ids=["no_d_ff", "no_kv_lora_rank", "no_vocab_size", "no_dtype",
        "head_dim_not_published", "not_a_field", "swapped_widths"])
def test_a_published_map_names_what_departs(change, named):
    published = {k: v for k, v in {**DEEPSEEK_V2_MAP, **change}.items()
                 if v is not None}
    with pytest.raises(ValueError) as e:
        H.program_config(_mapped(DEEPSEEK_V2, published, d_ff=12288))
    assert named in str(e.value)


def test_a_map_of_the_builtin_keys_checks_as_the_builtin():
    path = os.path.join(H.ROOT, "bench", "configs", "qwen3-14b.json")
    config = json.load(open(path))
    builtin = {"d_model": "hidden_size", "num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
               "vocab_size": "vocab_size", "d_ff": "intermediate_size",
               "tie_embeddings": "tie_word_embeddings",
               "dtype": "torch_dtype"}
    assert H.program_config(_mapped(config, builtin)) == \
        H.program_config(config)
    wide = dict(config, intermediate_size=17409)
    for c in (wide, _mapped(wide, builtin)):
        with pytest.raises(ValueError, match="'d_ff': \\(17408, 17409\\)"):
            H.program_config(c)
