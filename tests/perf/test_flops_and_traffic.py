"""The benchmark's own arithmetic: required FLOPs, the Zipf traffic and
the table of peaks."""

import json
from pathlib import Path

import numpy as np
import pytest

from perf import flops
from perf.peaks import PEAKS, peaks
from perf.traffic import zipf_tokens

REPO = Path(__file__).resolve().parents[2]


def _config(name):
    return json.loads((REPO / "perf/configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, seq, gflop, parts", [
    # 6N, causal attention, head: summed by hand from the published sizes
    ("gpt2-large", 1024, 4.92, (4.2503, 0.2831, 0.3860)),
    ("gpt2-large", 128, 4.67, (4.2503, 0.0354, 0.3860)),
    ("gpt2-xl", 1024, 9.81, (8.8534, 0.4719, 0.4825)),
])
def test_required_flops_per_token_against_hand_sums(name, seq, gflop, parts):
    from perf.families import gpt2
    c = _config(name)
    got = gpt2.flops_per_token(c, {"seq": seq}) / 1e9
    assert round(got, 2) == gflop
    assert got == pytest.approx(sum(parts), abs=2e-4)
    h, layers = c["n_embd"], c["n_layer"]
    six_n = 6 * (layers * (12 * h * h + 13 * h) + 2 * h) / 1e9
    attention = 6 * seq * h * layers / 1e9  # half of the full 12 L h S
    head = 6 * h * c["vocab_size"] / 1e9
    assert (six_n, attention, head) == pytest.approx(parts, abs=1e-4)
    # the program's own count takes attention in full and the padded head
    assert got < 6 * (layers * (12 * h * h + 13 * h) + 2 * h) / 1e9 \
        + 12 * layers * h * seq / 1e9 + 6 * h * 50304 / 1e9


def test_required_flops_against_the_flops_profiler_on_a_tiny_model():
    """The profiler counts what a traced program executes; with attention
    in full (the XLA path masks, it does not skip) and no recomputation,
    the matrix products it finds are the ones the function requires."""
    import jax
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.profiling import FlopsProfiler

    hidden, layers, seq, vocab, batch = 64, 2, 32, 256, 2
    model = GPT2Model(GPT2Config(
        vocab_size=vocab, n_positions=seq, hidden_size=hidden,
        num_layers=layers, num_heads=2, embd_dropout=0.0, attn_dropout=0.0,
        hidden_dropout=0.0, bf16=False, fused_loss=False))
    params = model.init_params(jax.random.PRNGKey(0))
    ids = np.zeros((batch, seq), np.int32)
    prof = FlopsProfiler()
    prof.start_profile()
    prof.profile_fn(jax.value_and_grad(lambda p: model.loss(p, None, ids)),
                    params)
    counted = prof.get_total_flops() / (batch * seq)
    need = flops.decoder_train_flops_per_token(hidden, layers, seq, vocab,
                                               causal=False)
    # the rest is element-wise work (LayerNorm, GELU, softmax), a few
    # percent at this width
    assert need <= counted <= 1.15 * need, (need, counted)


def test_flash_kernel_work_from_shapes():
    shape = (4, 20, 1024, 64)
    full = 2 * 4 * 20 * 1024 * 1024 * 64
    assert flops.flash_call_flops("flash_fwd", *shape) == 2 * full / 2
    assert flops.flash_call_flops("flash_bwd_dkdv", *shape) == 4 * full / 2
    assert flops.flash_call_flops("flash_bwd_dq", *shape,
                                  causal=False) == 3 * full
    assert flops.flash_call_bytes("flash_fwd", *shape) == 4 * 4 * 20 * 1024 \
        * 64 * 2
    peak = peaks("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(
        flops.flash_call_flops("flash_fwd", *shape),
        flops.flash_call_bytes("flash_fwd", *shape), peak)
    assert bound == "compute"
    assert seconds == pytest.approx(full / 197e12)
    assert flops.roofline_seconds(1.0, 1e9, peak)[1] == "memory"


def test_zipf_tokens_repeat_for_a_seed_and_differ_for_another():
    params = {"exponent": 1.0, "pool_steps": 4, "seq": 256}
    a = zipf_tokens.make(params, 8, 50257, seed=7)
    b = zipf_tokens.make(params, 8, 50257, seed=7)
    c = zipf_tokens.make(params, 8, 50257, seed=8)
    assert a.shape == (4, 8, 256) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 50257
    # a Zipf unigram: the first rank is drawn about 1 / H(50257) = 8.8%
    # of the time, and half of the draws come from a few hundred ranks
    assert 0.06 < np.mean(a == 0) < 0.12
    assert np.median(a) < 1000


def test_peaks_table_raises_on_an_unknown_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "cpu" not in PEAKS
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("TPU v9 imaginary")
