"""The model-work counts and the configuration files, against numbers
worked out by hand from the published granite shapes."""
import copy

import pytest

from bench import model, spec


def _cfg(name):
    return spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")


def test_granite_2b_params():
    assert model.Shape(_cfg("granite-3-2b")).params == 2_533_531_648


def test_granite_8b_params_published_and_one_stage():
    c = _cfg("granite-3.0-8b-l20")
    assert model.Shape(c).params == 4_186_095_616
    full = dict(c, num_hidden_layers=c["reduced"]["num_hidden_layers"])
    assert model.Shape(full).params == c["published_params"] == 8_170_848_256


def test_decode_step_work_by_hand():
    s = model.Shape(_cfg("granite-3-2b"))
    # one layer's matmul weights: QKV 2048x3072, W_o 2048x2048,
    # FFN in 2048x16384, FFN out 8192x2048
    W = 2048 * 3072 + 2048 * 2048 + 2048 * 16384 + 8192 * 2048
    assert s.layer_weights == W == 60_817_408
    flops, nbytes = model.step_work(s, [9, 99], [])
    keys = 10 + 100
    assert flops == 2 * W * 2 * 40 + 4 * 32 * 64 * keys * 40 \
        + 2 * 49155 * 2048 * 2
    kv_row = 2 * 8 * 64 * 2                 # K and V, bf16
    weights = 2 * (40 * W + 49155 * 2048) + 4 * (2 * 40 * 2048 + 2048)
    assert nbytes == weights + 40 * kv_row * (keys + 2) + 4 * 49155 * 2


def test_chunk_step_work_by_hand():
    s = model.Shape(_cfg("granite-3.0-8b-l20"))
    W = s.layer_weights
    # one decoding slot at position 0, one chunk of 100 rows at offset 128
    flops, nbytes = model.step_work(s, [0], [(128, 100)])
    keys = 1 + (100 * 128 + 100 * 101 // 2)
    assert flops == 2 * W * 101 * 20 + 4 * 32 * 128 * keys * 20 \
        + 2 * 49155 * 4096 * 2
    kv_row = 2 * 8 * 128 * 2
    weights = 2 * (20 * W + 49155 * 4096) + 4 * (2 * 20 * 4096 + 4096)
    assert nbytes == weights + 20 * kv_row * ((1 + 228) + 101) \
        + 4 * 49155 * 2


def test_model_config_matches_published_shape():
    cfg = model.model_config(_cfg("granite-3-2b"))
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == \
        (40, 2048, 32, 8, 64, 8192, 49155)
    assert cfg.tie_embeddings and cfg.dtype == "bfloat16"
    cfg8 = model.model_config(_cfg("granite-3.0-8b-l20"))
    assert (cfg8.num_layers, cfg8.d_model, cfg8.resolved_head_dim,
            cfg8.d_ff) == (20, 4096, 128, 12800)


@pytest.mark.parametrize("key,value", [("embedding_multiplier", 12.0),
                                       ("residual_multiplier", 0.22),
                                       ("logits_scaling", 8.0)])
def test_model_config_refuses_what_the_program_cannot_run(key, value):
    c = copy.deepcopy(_cfg("granite-3-2b"))
    c[key] = value
    with pytest.raises(ValueError, match=key):
        model.model_config(c)


def test_reduced_keys_are_listed_in_the_benchmark():
    bm = spec.load_benchmark()
    for entry in bm["configs"]:
        c = spec.load_json(spec.ROOT / entry["file"])
        assert sorted(entry["reduced"]) == sorted(c["reduced"])
        assert entry["source"] == c["source"]


def test_seed_words_take_any_whole_number():
    for seed in (0, 7, 2 ** 31 + 3, 2 ** 63 + 9, -1):
        lo, hi = model.seed_words(seed)
        assert 0 <= lo < 2 ** 31 and 0 <= hi < 2 ** 31
    assert model.seed_words(5) == model.seed_words(5)
    assert model.seed_words(5) != model.seed_words(6)
