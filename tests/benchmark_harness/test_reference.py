"""The plain reference agrees with the program's own model at a small size
(GQA, a rope_theta that is not the default), and the seeded weights reach the
program as the reference draws them."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import llama_ref

CFG = dict(hidden_size=64, intermediate_size=160, num_attention_heads=8,
           num_key_value_heads=2, vocab_size=512, num_hidden_layers=3,
           rms_norm_eps=1e-5, rope_theta=500000.0, tie_word_embeddings=False,
           torch_dtype="float32", initializer_range=0.02)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    lc = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=160,
                     num_hidden_layers=3, num_attention_heads=8,
                     num_key_value_heads=2, max_position_embeddings=1024,
                     rope_theta=500000.0, rms_norm_eps=1e-5,
                     tensor_parallel=False)
    m = LlamaForCausalLM(lc)
    m.eval()
    weights.load_into(m, CFG, seed=2**31 + 3)
    return m


def test_loaded_weights_are_the_seeded_ones(model):
    params, _ = model.functional_state()
    key = weights.seed_key(2**31 + 3)
    want = weights.make(key, CFG)
    assert len(want) == len(params) == 3 * 9 + 3
    for name, v in want.items():
        got = params[weights.program_name(name)]
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(v), err_msg=name)
    layer1 = weights.make_layer(key, CFG, jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(layer1["gate"]),
                                  np.asarray(want["layers.1.gate"]))
    assert float(jnp.std(want["layers.0.q"])) == pytest.approx(weights.INIT_STD, rel=0.03)
    assert np.all(np.asarray(want["layers.2.ln2"]) == 1)


def test_reference_logits_agree_with_the_programs_model(model):
    import paddle_tpu as paddle

    rng = np.random.default_rng(0)
    toks = rng.integers(0, 512, (2, 1024), dtype=np.int32)
    got = np.asarray(model(paddle.to_tensor(toks))._value)
    want = np.asarray(llama_ref.full_logits(CFG, 2**31 + 3, toks))
    assert want.shape == got.shape == (2, 1024, 512)
    # float32 both sides; the program's products run at the backend's default
    # precision, the reference at "highest": agreement to rounding of
    # logits of size ~0.2
    np.testing.assert_allclose(got, want, atol=2e-4)
    # and it is the rope_theta that makes them agree
    other = np.asarray(llama_ref.full_logits(dict(CFG, rope_theta=10000.0), 2**31 + 3, toks))
    assert np.abs(other - want).max() > 50 * np.abs(got - want).max()


def test_served_gap_is_zero_for_the_references_own_choice():
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 512, 40, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):  # greedy decoding by the reference itself
        lg = llama_ref.full_logits(CFG, 7, np.asarray([seq + [0] * (512 - len(seq))], np.int32))
        seq.append(int(jnp.argmax(lg[0, len(seq) - 1])))
    out = np.asarray(seq[40:], np.int32)
    gaps, control = llama_ref.served_gap(CFG, 7, [(prompt, out)], 512, quant="fp8")
    assert gaps.shape == (6,) and np.all(gaps == 0)
    wrong = out.copy()
    wrong[2] = (wrong[2] + 1) % 512
    gaps, _ = llama_ref.served_gap(CFG, 7, [(prompt, wrong)], 512)
    assert gaps[2] > 0 and np.all(gaps[:2] == 0)
    assert control.shape == (6,) and np.all(control >= 0)


@pytest.mark.parametrize("quant", ["bf16", "fp8", "int8"])
def test_lower_precisions_round(quant):
    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 64)), jnp.float32)
    y = llama_ref._q(x, quant)
    err = float(jnp.max(jnp.abs(y - x)) / jnp.max(jnp.abs(x)))
    assert 0 < err < {"bf16": 2**-8, "fp8": 2**-3, "int8": 1 / 127}[quant]
