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
    c = _cfg("granite-3-2b")
    s = model.Shape(c)
    # one layer's matmul weights: QKV 2048x3072, W_o 2048x2048,
    # FFN in 2048x16384, FFN out 8192x2048
    W = 2048 * 3072 + 2048 * 2048 + 2048 * 16384 + 8192 * 2048
    assert s.layer_weights == W == 60_817_408
    flops, nbytes = model.step_work(c, [9, 99], [])
    keys = 10 + 100
    assert flops == 2 * W * 2 * 40 + 4 * 32 * 64 * keys * 40 \
        + 2 * 49155 * 2048 * 2
    kv_row = 2 * 8 * 64 * 2                 # K and V, bf16
    weights = 2 * (40 * W + 49155 * 2048) + 4 * (2 * 40 * 2048 + 2048)
    assert nbytes == weights + 40 * kv_row * (keys + 2) + 4 * 49155 * 2


def test_chunk_step_work_by_hand():
    c = _cfg("granite-3.0-8b-l20")
    W = model.Shape(c).layer_weights
    # one decoding slot at position 0, one chunk of 100 rows at offset 128
    flops, nbytes = model.step_work(c, [0], [(128, 100)])
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


# -- the architecture's code is a file: bench/archs/<model_type>.py --------

FIX = spec.ROOT / "tests" / "bench" / "fixtures"
READ = spec.load_json(FIX / "granite-readings.json")


def _named(name):
    return spec.load_json(FIX / "tiny-granite.json") \
        if name == "tiny-granite" else _cfg(name)


@pytest.mark.parametrize("name", sorted(READ["shapes"]))
def test_granite_shape_reads_as_before_the_move(name):
    s = model.Shape(_named(name))
    assert {k: getattr(s, k) for k in READ["shapes"][name]} == \
        READ["shapes"][name]


@pytest.mark.parametrize("name,case", [
    (name, i) for name in sorted(READ["work"])
    for i in range(len(READ["work"][name]))])
def test_granite_step_work_reads_as_before_the_move(name, case):
    pos, chunks, want = READ["work"][name][case]
    got = model.step_work(_named(name), pos, [tuple(ch) for ch in chunks])
    assert list(got) == want


@pytest.mark.parametrize("kind", ["f32", "int8"])
def test_granite_reference_logits_read_as_before_the_move(kind):
    import jax
    import jax.numpy as jnp
    import numpy as np
    want = READ["logits"][kind]
    c = _named("tiny-granite")
    params = model.init_weights(c, want["seed"])
    fwd = model.arch(c).forward
    lg = np.asarray(jax.jit(lambda p, t: fwd(c, p, t, kind == "int8"))(
        params, jnp.asarray(want["tokens"], jnp.int32)))
    np.testing.assert_array_equal(lg[:, ::37],
                                  np.asarray(want["every_37th"], np.float32))
    np.testing.assert_allclose(lg.sum(-1), want["row_sums"], rtol=1e-6)


def test_unknown_model_type_names_the_file_to_add():
    c = dict(_cfg("granite-3-2b"), model_type="nosuchmodel")
    for fn in (model.Shape, model.model_config,
               lambda c: model.step_work(c, [0], [])):
        with pytest.raises(ValueError, match=r"archs/nosuchmodel\.py"):
            fn(c)


TOY = '''\
"""A throwaway architecture: granite's, with every W_o drawn as zeros."""
from bench import model

GRANITE = model.arch({"model_type": "granite"})


class Shape(GRANITE.Shape):
    @property
    def params(self):
        return -1


def model_config(c):
    return GRANITE.model_config(c)


def step_work(c, active_pos, chunks):
    return (1.0, 2.0)


def forward(c, params, tokens, int8):
    return GRANITE.forward(c, params, tokens, int8)


def leaf_std(names, shape):
    return 0.0 if names[-1] == "w_o" else None
'''


def test_an_architecture_is_added_as_a_file(tmp_path, monkeypatch):
    import shutil

    import numpy as np
    archs = tmp_path / "archs"
    shutil.copytree(model.ARCH_DIR, archs)
    (archs / "toy.py").write_text(TOY)
    monkeypatch.setattr(model, "ARCH_DIR", archs)
    c = dict(spec.load_json(FIX / "tiny-granite.json"), model_type="toy")
    assert model.Shape(c).params == -1
    assert model.step_work(c, [3], []) == (1.0, 2.0)
    assert model.model_config(c).d_model == 64
    params = model.init_weights(c, 11)
    run = next(k for k in params if k.startswith("run"))
    assert not np.asarray(params[run]["attn"]["w_o"], np.float32).any()
    assert np.asarray(params[run]["attn"]["w_qkv"], np.float32).any()
    # granite itself is untouched by the new file
    g = spec.load_json(FIX / "tiny-granite.json")
    assert model.Shape(g).params == READ["shapes"]["tiny-granite"]["params"]
