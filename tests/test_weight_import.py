"""Pretrained-weight import (VERDICT r3 item 6): npz/safetensors →
functional-LM pytree with a shape/name report.  The gold test checks
logit equivalence against transformers' own GPT2LMHeadModel on an
imported GPT-2-format checkpoint — transposes, fused-qkv splits, biases,
LN epsilon and gelu flavor all have to be right for it to pass."""

import json
import struct

import numpy as np
import pytest

from fedml_tpu.models.functional_lm import init_lm_params, lm_forward
from fedml_tpu.train.llm.weight_import import (
    export_lm_weights,
    import_lm_weights,
    read_checkpoint,
    save_lm_checkpoint,
)

import jax
import jax.numpy as jnp


def _full_attn(q, k, v):
    """Reference causal attention for equivalence tests: [B,H,T,Dh]."""
    dh = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(dh))
    t = q.shape[2]
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -1e9)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


def test_native_roundtrip(tmp_path):
    params = init_lm_params(jax.random.PRNGKey(0), vocab=50, dim=32,
                            layers=2, heads=4, max_len=16)
    path = str(tmp_path / "lm.npz")
    save_lm_checkpoint(params, path)
    loaded, report = import_lm_weights(path, schema="auto")
    assert not report["missing"] and not report["unused"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        params, loaded)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 16)))
    np.testing.assert_allclose(
        np.asarray(lm_forward(params, toks, 4, _full_attn)),
        np.asarray(lm_forward(loaded, toks, 4, _full_attn)), atol=1e-6)


def test_gpt2_import_matches_transformers_logits(tmp_path):
    """Build a tiny random GPT-2 with transformers, export its state dict
    to npz, import through the mapper, and require logit agreement."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    cfg = transformers.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    model = transformers.GPT2LMHeadModel(cfg).eval()

    sd = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
    path = str(tmp_path / "gpt2.npz")
    np.savez(path, **sd)

    params, report = import_lm_weights(path, schema="auto")
    assert not report["missing"], report["missing"]
    # everything in the file is either mapped or a structural mask buffer
    assert not report["unused"], report["unused"]

    toks_np = np.random.RandomState(0).randint(0, 64, (2, 16))
    with torch.no_grad():
        ref = model(torch.from_numpy(toks_np)).logits.numpy()
    ours = np.asarray(lm_forward(params, jnp.asarray(toks_np), 4,
                                 _full_attn))
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=1e-3)


def test_safetensors_stdlib_reader(tmp_path):
    """The dependency-free .safetensors parser reads what the format
    spec says: 8-byte header length + JSON header + raw little-endian
    buffer (bf16 widened to f32)."""
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    b32 = np.asarray(jnp.asarray([[1.5, -2.0]], jnp.bfloat16))
    raw_a = a.tobytes()
    u16 = np.asarray(jnp.asarray(b32, jnp.bfloat16)).view(np.uint16)
    raw_b = u16.tobytes()
    header = {
        "a": {"dtype": "F32", "shape": [2, 3],
              "data_offsets": [0, len(raw_a)]},
        "b": {"dtype": "BF16", "shape": [1, 2],
              "data_offsets": [len(raw_a), len(raw_a) + len(raw_b)]},
    }
    hb = json.dumps(header).encode()
    path = tmp_path / "t.safetensors"
    path.write_bytes(struct.pack("<Q", len(hb)) + hb + raw_a + raw_b)

    # force the stdlib path even if the safetensors lib is installed
    from fedml_tpu.train.llm import weight_import as wi

    state = wi._read_safetensors(str(path))
    np.testing.assert_array_equal(state["a"], a)
    np.testing.assert_allclose(state["b"], np.asarray(b32, np.float32))


def test_trainer_finetunes_from_imported_weights(tmp_path):
    """finetune-from-imported-weights end to end: import → LLMTrainer →
    loss decreases from the pretrained starting point."""
    import fedml_tpu
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    params = init_lm_params(jax.random.PRNGKey(1), vocab=90, dim=32,
                            layers=1, heads=4, max_len=64)
    path = str(tmp_path / "pretrained.npz")
    save_lm_checkpoint(params, path)

    args = fedml_tpu.Config(model="functional_lm", dataset="shakespeare",
                            lm_dim=32, lm_layers=1, lm_heads=4,
                            lm_max_len=64, compute_dtype="float32")
    bundle = fedml_tpu.model.create(args, 90)
    cfg = LLMTrainConfig(seq_len=32, batch_size=4, learning_rate=3e-3,
                         epochs=2, use_lora=False,
                         pretrained_path=path)
    tr = LLMTrainer(bundle, cfg)
    assert tr.import_report and not tr.import_report["missing"]
    # the trainer actually starts FROM the imported weights
    np.testing.assert_array_equal(
        np.asarray(tr.variables["params"]["embed"]),
        np.asarray(params["embed"]))

    rng = np.random.RandomState(0)
    token_ids = rng.randint(0, 90, 8 * 4 * 33)
    out = tr.train(token_ids)
    hist = out["loss_history"]
    assert hist[-1] < hist[0]
    assert np.isfinite(out["train_loss"])


def test_kv_cache_serving_matches_forward_on_imported_gpt2(tmp_path):
    """The KV-cache serving path (prefill + decode_multi) must reproduce
    lm_forward on an imported checkpoint WITH biases — it hands the shared
    block its own attention, so a bias lost on the way to the cache would
    silently serve wrong logits."""
    transformers = pytest.importorskip("transformers")

    cfg = transformers.GPT2Config(
        vocab_size=48, n_positions=24, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    model = transformers.GPT2LMHeadModel(cfg).eval()
    sd = {k: v.detach().cpu().numpy()
          for k, v in model.state_dict().items()}
    params, report = import_lm_weights(sd, schema="gpt2")
    assert not report["missing"]

    from fedml_tpu.serving.kv_cache_lm import decode_multi, prefill

    toks_np = np.random.RandomState(1).randint(0, 48, (2, 10))
    toks = jnp.asarray(toks_np)
    full = np.asarray(lm_forward(params, toks, 4, _full_attn))

    length = jnp.asarray([10, 10])
    cache, last = prefill(params, toks, length, heads=4, max_len=16)
    np.testing.assert_allclose(np.asarray(last), full[:, -1], atol=1e-4,
                               rtol=1e-3)

    # one greedy decode step (k = 1) == forward over the extended sequence:
    # its token is the last logits' best, and the K/V it stored at position
    # 10 are those a prefill of the extended sequence computes there
    nxt = jnp.asarray(np.random.RandomState(2).randint(0, 48, (2,)))
    zeros = jnp.zeros((2,), jnp.float32)
    cache, emitted = decode_multi(
        params, cache, nxt[:, None].astype(jnp.int32),
        jnp.ones((2,), jnp.int32), jnp.asarray([10, 10], jnp.int32), zeros,
        jnp.zeros((2,), jnp.int32), zeros + 1, jax.random.PRNGKey(0),
        heads=4, k=1)
    ext = jnp.concatenate([toks, nxt[:, None]], axis=1)
    full_ext = np.asarray(lm_forward(params, ext, 4, _full_attn))
    best = np.sort(full_ext[:, -1], axis=-1)
    assert (best[:, -1] - best[:, -2] > 1e-3).all()      # a clear winner
    np.testing.assert_array_equal(np.asarray(emitted)[:, 0],
                                  full_ext[:, -1].argmax(-1))
    ext_cache, _ = prefill(params, ext, jnp.asarray([11, 11]), heads=4,
                           max_len=16)
    for got, want in zip(cache, ext_cache):
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(got[name])[..., :11],
                np.asarray(want[name])[..., :11], atol=1e-4, rtol=1e-3)


def test_biasfree_gpt2_schema_passes_strict_and_mismatch_raises():
    """Biases are optional (strict must not fail on a bias-free gpt2-named
    checkpoint), while dim/vocab/head mismatches must raise loudly —
    JAX would otherwise clamp out-of-bounds gathers silently."""
    from fedml_tpu.train.llm.weight_import import validate_lm_shapes

    params = init_lm_params(jax.random.PRNGKey(2), vocab=32, dim=16,
                            layers=1, heads=4, max_len=8)
    # build a bias-free gpt2-style dict from our own params
    sd = {
        "wte.weight": np.asarray(params["embed"]),
        "wpe.weight": np.asarray(params["pos"]),
        "ln_f.weight": np.asarray(params["ln_f"]["scale"]),
        "ln_f.bias": np.asarray(params["ln_f"]["bias"]),
    }
    blk = params["blocks"][0]
    sd["h.0.ln_1.weight"] = np.asarray(blk["ln1"]["scale"])
    sd["h.0.ln_1.bias"] = np.asarray(blk["ln1"]["bias"])
    sd["h.0.ln_2.weight"] = np.asarray(blk["ln2"]["scale"])
    sd["h.0.ln_2.bias"] = np.asarray(blk["ln2"]["bias"])
    sd["h.0.attn.c_attn.weight"] = np.concatenate(
        [np.asarray(blk[k]) for k in ("wq", "wk", "wv")], axis=1)
    sd["h.0.attn.c_proj.weight"] = np.asarray(blk["wo"])
    sd["h.0.mlp.c_fc.weight"] = np.asarray(blk["w1"])
    sd["h.0.mlp.c_proj.weight"] = np.asarray(blk["w2"])

    loaded, report = import_lm_weights(sd, schema="gpt2", strict=True)
    assert not report["missing"]
    assert report["optional_absent"]          # the absent biases, recorded
    toks = jnp.asarray(np.random.RandomState(3).randint(0, 32, (1, 8)))
    np.testing.assert_allclose(
        np.asarray(lm_forward(params, toks, 4, _full_attn)),
        np.asarray(lm_forward(loaded, toks, 4, _full_attn)), atol=1e-6)

    validate_lm_shapes(loaded, vocab=32, dim=16, heads=4, min_len=8)
    with pytest.raises(ValueError, match="vocab"):
        validate_lm_shapes(loaded, vocab=64)
    with pytest.raises(ValueError, match="heads"):
        validate_lm_shapes(loaded, heads=3)
    with pytest.raises(ValueError, match="max_len"):
        validate_lm_shapes(loaded, min_len=999)


@pytest.mark.slow
def test_openai_serving_from_imported_gpt2_checkpoint(tmp_path):
    """Deploy half of the import loop: GPT-2-format checkpoint FILE →
    kv_lm_from_checkpoint → continuous-batching engine → OpenAI chat API.
    Greedy first token must equal transformers' own argmax next token."""
    import urllib.request

    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from fedml_tpu.serving.kv_cache_lm import kv_lm_from_checkpoint
    from fedml_tpu.serving.llm_engine import (
        KVCacheLLMEngine,
        LLMEnginePredictor,
    )
    from fedml_tpu.serving.openai_api import OpenAIServer

    cfg = transformers.GPT2Config(
        vocab_size=90, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    model = transformers.GPT2LMHeadModel(cfg).eval()
    path = str(tmp_path / "gpt2_tiny.npz")
    np.savez(path, **{k: v.detach().cpu().numpy()
                      for k, v in model.state_dict().items()})

    lm = kv_lm_from_checkpoint(path, heads=4)
    assert lm.vocab == 90 and lm.max_len == 64
    engine = KVCacheLLMEngine(lm, max_batch=2)
    predictor = LLMEnginePredictor(engine)      # char codec, vocab 90
    server = OpenAIServer(predictor, model_name="gpt2-tiny", port=0)
    try:
        server.run(block=False)
        body = json.dumps({"model": "gpt2-tiny", "max_tokens": 4,
                            "temperature": 0,
                            "messages": [{"role": "user",
                                          "content": "hello"}]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/chat/completions",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            out = json.loads(resp.read())
        text = out["choices"][0]["message"]["content"]
        assert len(text) == 4

        # greedy first token must sit at (or within float tolerance of)
        # transformers' argmax — random-init logits can tie to ~1e-4, so
        # exact-id equality would flake on tie-breaks
        # the server wraps messages in its chat template — compare on the
        # exact prompt the engine saw
        ids = predictor.encode("user: hello\nassistant:")
        with torch.no_grad():
            ref_logits = model(torch.tensor([ids])).logits[0, -1].numpy()
        ours = predictor.encode(text[0])[0]
        assert ref_logits[ours] >= ref_logits.max() - 1e-3, (
            text[0], float(ref_logits[ours]), float(ref_logits.max()))
    finally:
        server.stop()
        engine.stop()
