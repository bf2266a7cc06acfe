"""BERT MLM + text pipeline tests (config 3, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.data.feed import host_batches, put_global
from distributeddeeplearningspark_tpu.models import bert_large, bert_tiny
from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
from distributeddeeplearningspark_tpu.parallel.sharding import REPLICATED
from distributeddeeplearningspark_tpu.train import losses, optim, step as step_lib


def build_tokenizer():
    docs = text_lib.synthetic_wikipedia(64, num_partitions=2, seed=1)
    return text_lib.WordPieceTokenizer.train(docs.collect(), vocab_size=512)


class TestTokenizer:
    def test_roundtrip_known_words(self):
        tok = build_tokenizer()
        ids = tok.encode("the history of the city")
        assert len(ids) and all(i not in (tok.unk_id,) for i in ids)
        assert tok.decode(ids) == "the history of the city"

    def test_char_fallback_no_unk(self):
        tok = build_tokenizer()
        # unseen word decomposes into char pieces, not UNK
        ids = tok.tokenize_word("zzzq")
        assert tok.unk_id not in ids or len(ids) == 1

    def test_save_load(self, tmp_path):
        tok = build_tokenizer()
        path = str(tmp_path / "vocab.txt")
        tok.save(path)
        tok2 = text_lib.WordPieceTokenizer.load(path)
        assert tok2.vocab == tok.vocab


class TestMasking:
    def test_shapes_and_mask_rate(self):
        tok = build_tokenizer()
        rng = np.random.default_rng(0)
        ids = np.array([tok.cls_id] + [10] * 126 + [tok.sep_id], np.int32)
        ex = text_lib.mask_tokens(ids, tok, rng)
        assert ex["input_ids"].shape == (128,)
        assert ex["mlm_labels"].shape == (128,)
        rate = ex["mlm_weights"].mean()
        assert 0.05 < rate < 0.30  # ~15%
        # specials never masked
        assert ex["mlm_weights"][0] == 0 and ex["mlm_weights"][-1] == 0
        # labels hold the ORIGINAL ids everywhere
        assert (ex["mlm_labels"] == ids).all()

    def test_pipeline_example_schema(self):
        tok = build_tokenizer()
        docs = text_lib.synthetic_wikipedia(16, num_partitions=2)
        ds = text_lib.mlm_dataset(docs, tok, seq_len=64)
        ex = ds.first()
        assert set(ex) == {"input_ids", "attention_mask", "mlm_labels", "mlm_weights"}
        assert all(v.shape == (64,) for v in ex.values())


def test_bert_forward_shapes():
    model = bert_tiny()
    batch = {
        "input_ids": np.ones((2, 32), np.int32),
        "attention_mask": np.ones((2, 32), np.int32),
    }
    variables = model.init(jax.random.PRNGKey(0), batch, train=False)
    logits = model.apply(variables, batch, train=False)
    assert logits.shape == (2, 32, model.cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_bert_large_geometry_param_count():
    """BertConfig.large must be the published BERT-large: ~340M params
    (Devlin et al. Table 1), counted abstractly via eval_shape — no 340M
    f32 init on the test host."""
    model = bert_large()
    batch = {"input_ids": jax.ShapeDtypeStruct((1, 16), np.int32)}
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch, train=False))
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(abstract))
    assert 3.2e8 < n < 3.6e8, n


def test_tied_decoder_shares_embedding():
    """The MLM decoder must reuse the token-embedding table (no second one)."""
    model = bert_tiny()
    batch = {"input_ids": np.ones((1, 16), np.int32)}
    variables = model.init(jax.random.PRNGKey(0), batch, train=False)
    flat = jax.tree_util.tree_flatten_with_path(variables["params"])[0]
    emb_tables = [p for p, v in flat if any("embedding" in str(k) for k in p)
                  and v.shape[-1] == model.cfg.hidden_size
                  and v.shape[0] == model.cfg.vocab_size]
    assert len(emb_tables) == 1  # token table exists once, not duplicated


def test_bert_mlm_learns(eight_devices):
    """DP MLM training on 8 fake chips: loss drops, masked acc beats chance."""
    mesh = MeshSpec(data=8).build(eight_devices)
    tok = build_tokenizer()
    model = bert_tiny(vocab_size=tok.vocab_size, num_layers=2, hidden_size=64,
                      num_heads=2, intermediate_size=128, dropout_rate=0.0)
    docs = text_lib.synthetic_wikipedia(256, num_partitions=8)
    ds = text_lib.mlm_dataset(docs, tok, seq_len=64).repeat()
    feed = host_batches(ds, 32, num_shards=8)

    tx = optim.adamw(optim.warmup_linear(3e-3, 10, 80))
    batch = next(feed)
    state, shardings = step_lib.init_state(model, tx, batch, mesh, REPLICATED)
    train_step = step_lib.jit_train_step(
        step_lib.make_train_step(model.apply, tx, losses.masked_lm),
        mesh, shardings,
    )
    first = last = None
    for i, hb in enumerate(feed):
        if i >= 60:
            break
        state, m = train_step(state, put_global(hb, mesh))
        if first is None:
            first = float(m["loss"])
        last = m
    assert float(last["loss"]) < first * 0.8
    assert float(last["mlm_accuracy"]) > 2.0 / tok.vocab_size


def test_gathered_mlm_head_matches_full_length():
    """mlm_positions gather: same loss/grads as the full-length head on the
    same targets (the original TPU BERT masked_lm_positions design)."""
    import optax

    from distributeddeeplearningspark_tpu.data.text import pack_mlm_predictions
    from distributeddeeplearningspark_tpu.models import bert_tiny
    from distributeddeeplearningspark_tpu.train import losses

    model = bert_tiny()
    V = model.cfg.vocab_size
    rng = np.random.default_rng(0)
    b, s, p = 2, 32, 8
    full = {
        "input_ids": rng.integers(0, V, (b, s)).astype(np.int32),
        "attention_mask": np.ones((b, s), np.int32),
        "mlm_labels": rng.integers(0, V, (b, s)).astype(np.int32),
        "mlm_weights": np.zeros((b, s), np.float32),
    }
    for i in range(b):  # 5 masked positions per row (< p)
        full["mlm_weights"][i, rng.choice(s, 5, replace=False)] = 1.0
    packed_rows = [pack_mlm_predictions(
        {k: v[i] for k, v in full.items()}, p) for i in range(b)]
    packed = {k: np.stack([r[k] for r in packed_rows]) for k in packed_rows[0]}

    variables = model.init(jax.random.PRNGKey(0), full, train=False)

    def loss_for(batch):
        def f(params):
            logits = model.apply({"params": params}, batch, train=False)
            return losses.masked_lm(logits, batch)[0]
        return f

    lf = jax.value_and_grad(loss_for(full))(variables["params"])
    lp = jax.value_and_grad(loss_for(packed))(variables["params"])
    np.testing.assert_allclose(float(lf[0]), float(lp[0]), rtol=2e-5)
    for a, b2 in zip(jax.tree.leaves(lf[1]), jax.tree.leaves(lp[1])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b2, np.float32),
                                   rtol=5e-3, atol=2e-5)


def test_mlm_dataset_packed_form():
    from distributeddeeplearningspark_tpu.data.text import (
        WordPieceTokenizer, mlm_dataset)
    from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

    tok = WordPieceTokenizer.train(
        ["the quick brown fox jumps over the lazy dog"] * 20, vocab_size=64)
    docs = PartitionedDataset.parallelize(
        ["the quick brown fox jumps over the lazy dog"] * 8, 2)
    ds = mlm_dataset(docs, tok, seq_len=16, max_predictions=4, seed=1)
    ex = ds.take(3)[1]
    assert set(ex) == {"input_ids", "attention_mask", "mlm_positions",
                      "mlm_labels", "mlm_weights"}
    assert ex["mlm_positions"].shape == (4,)
    assert ex["mlm_weights"].sum() >= 1
    # packed labels must equal the full-length example's ORIGINAL tokens at
    # the packed positions — verify against an identically-seeded unpacked run
    ds_full = mlm_dataset(docs, tok, seq_len=16, seed=1)
    full = ds_full.take(3)[1]
    for j in range(4):
        if ex["mlm_weights"][j] > 0:
            assert ex["mlm_labels"][j] == full["mlm_labels"][ex["mlm_positions"][j]]
            assert full["mlm_weights"][ex["mlm_positions"][j]] > 0
    # and the packed input_ids are the same corrupted stream
    np.testing.assert_array_equal(ex["input_ids"], full["input_ids"])


def _tiny_hf_bert():
    transformers = __import__("pytest").importorskip("transformers")
    HFBertConfig = transformers.BertConfig
    FlaxBertForMaskedLM = transformers.FlaxBertForMaskedLM

    hf_cfg = HFBertConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    return FlaxBertForMaskedLM(hf_cfg, seed=0), hf_cfg


def test_hf_bert_import_logits_parity():
    """import_hf_bert: our BertForMLM reproduces FlaxBertForMaskedLM logits
    on the same (randomly initialized) weights — full numerical parity of
    embeddings, encoder stack, and tied MLM head."""
    from distributeddeeplearningspark_tpu.models.bert import BertConfig, BertForMLM
    from distributeddeeplearningspark_tpu.models.bert_io import import_hf_bert

    hf_model, hf_cfg = _tiny_hf_bert()
    cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     intermediate_size=128, max_position=64,
                     dropout_rate=0.0, dtype=jnp.float32, attention_impl="xla")
    params = import_hf_bert(hf_model.params, cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 16)).astype(np.int32)
    attn = np.ones((2, 16), np.int32)
    attn[1, 12:] = 0
    ours = BertForMLM(cfg).apply(
        {"params": params},
        {"input_ids": jnp.asarray(ids), "attention_mask": jnp.asarray(attn)},
        train=False)
    theirs = hf_model(input_ids=ids, attention_mask=attn).logits
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=2e-4, atol=2e-4)


def test_hf_bert_export_round_trip():
    from distributeddeeplearningspark_tpu.models.bert import BertConfig
    from distributeddeeplearningspark_tpu.models.bert_io import (
        export_hf_bert, import_hf_bert)

    hf_model, _ = _tiny_hf_bert()
    cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     intermediate_size=128, max_position=64)
    ours = import_hf_bert(hf_model.params, cfg)
    back = export_hf_bert(ours, cfg)
    again = import_hf_bert(back, cfg)
    flat_a = jax.tree_util.tree_flatten_with_path(ours)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(again)[0]
    assert len(flat_a) == len(flat_b)
    for (pa, a), (pb, b) in zip(flat_a, flat_b):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hf_bert_torch_import_matches_flax_import():
    import pytest

    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    HFBertConfig, BertForMaskedLM = transformers.BertConfig, transformers.BertForMaskedLM

    from distributeddeeplearningspark_tpu.models.bert import BertConfig, BertForMLM
    from distributeddeeplearningspark_tpu.models.bert_io import import_hf_bert_torch

    hf_cfg = HFBertConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    torch.manual_seed(0)
    tmodel = BertForMaskedLM(hf_cfg).eval()
    cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     intermediate_size=128, max_position=64,
                     dropout_rate=0.0, dtype=jnp.float32, attention_impl="xla")
    params = import_hf_bert_torch(tmodel.state_dict(), cfg)

    rng = np.random.default_rng(1)
    ids = rng.integers(0, 256, (2, 16)).astype(np.int32)
    attn = np.ones((2, 16), np.int32)
    ours = BertForMLM(cfg).apply(
        {"params": params},
        {"input_ids": jnp.asarray(ids), "attention_mask": jnp.asarray(attn)},
        train=False)
    with torch.no_grad():
        theirs = tmodel(input_ids=torch.tensor(ids.astype(np.int64)),
                        attention_mask=torch.tensor(attn.astype(np.int64))).logits
    np.testing.assert_allclose(np.asarray(ours), theirs.numpy(),
                               rtol=2e-4, atol=2e-4)


class TestSequencePacking:
    """VERDICT r2 #4: packing honesty — packed windows are ~pad-free, the
    naive per-document mode is mostly padding, segment ids isolate documents."""

    def test_packed_windows_full_and_segmented(self):
        tok = build_tokenizer()
        docs = text_lib.synthetic_wikipedia(24, num_partitions=1).collect()
        pairs = list(text_lib.packed_segments_from_docs(docs, tok, 64))
        assert len(pairs) >= 2
        for ids, sids in pairs[:-1]:  # all but corpus tail: zero padding
            assert ids.shape == (64,) and sids.shape == (64,)
            assert not (ids == tok.pad_id).any()
            # segment ids are a nondecreasing doc counter within the window
            assert (np.diff(sids[1:-1]) >= 0).all()
        ids, sids = pairs[-1]
        assert ((ids == tok.pad_id) == (sids == -1)).all()

    def test_padded_mode_mostly_padding(self):
        tok = build_tokenizer()
        docs = text_lib.synthetic_wikipedia(32, num_partitions=2)
        packed = text_lib.mlm_dataset(docs, tok, seq_len=512)
        naive = text_lib.mlm_dataset(docs, tok, seq_len=512, pack=False)
        s_packed = text_lib.token_stats(packed)
        s_naive = text_lib.token_stats(naive)
        # synthetic docs are 60–120 words → well under 512 tokens each
        assert s_naive["pad_frac"] > 0.5
        assert s_packed["pad_frac"] < 0.1
        assert s_packed["effective_frac"] > s_naive["effective_frac"] + 0.4

    def test_mlm_dataset_emits_segment_ids(self):
        tok = build_tokenizer()
        docs = text_lib.synthetic_wikipedia(16, num_partitions=2)
        ex = text_lib.mlm_dataset(docs, tok, seq_len=64,
                                  segment_ids=True).first()
        assert "segment_ids" in ex and ex["segment_ids"].shape == (64,)
        # gathered form passes them through
        ex2 = text_lib.mlm_dataset(docs, tok, seq_len=64, segment_ids=True,
                                   max_predictions=12).first()
        assert "segment_ids" in ex2 and ex2["segment_ids"].shape == (64,)
        assert ex2["mlm_positions"].shape == (12,)

    def test_bert_consumes_segment_ids(self):
        """Packed batch with segment ids runs through the model, and doc
        isolation changes the output vs ignoring the ids."""
        model = bert_tiny(num_layers=1, hidden_size=32, num_heads=2,
                          intermediate_size=64, dropout_rate=0.0)
        rng = np.random.default_rng(5)
        ids = rng.integers(10, 500, (2, 32)).astype(np.int32)
        segs = np.zeros((2, 32), np.int32)
        segs[:, 16:] = 1
        batch = {"input_ids": ids, "attention_mask": np.ones_like(ids)}
        variables = model.init(jax.random.PRNGKey(0), batch, train=False)
        plain = model.apply(variables, batch, train=False)
        packed = model.apply(variables, {**batch, "segment_ids": segs},
                             train=False)
        assert np.isfinite(np.asarray(packed)).all()
        assert not np.allclose(np.asarray(plain), np.asarray(packed))
        # isolation: with segment ids, doc 0's logits equal running doc 0
        # alone (positions are absolute either way)
        alone = model.apply(
            variables,
            {"input_ids": ids[:, :16],
             "attention_mask": np.ones((2, 16), np.int32)},
            train=False)
        np.testing.assert_allclose(np.asarray(packed)[:, :16],
                                   np.asarray(alone), atol=1e-5, rtol=1e-5)
