"""Text pipeline tests — raw strings to trained model (counterpart of the
reference's ``feature/text`` specs + ``TextClassifier`` examples), including
a BERT-small classifier fine-tune (start of parity config #4)."""

import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.feature.text import TextSet


def _corpus(n_per=40, seed=0):
    """Two topics with distinct vocabularies + shared filler words."""
    rng = np.random.default_rng(seed)
    sports = "game team goal score win match player league".split()
    cooking = "recipe oven bake flour sugar dish taste kitchen".split()
    filler = "the a of and to in it is was for".split()
    texts, labels = [], []
    for label, vocab in ((0, sports), (1, cooking)):
        for _ in range(n_per):
            words = [vocab[rng.integers(len(vocab))] for _ in range(6)]
            words += [filler[rng.integers(len(filler))] for _ in range(6)]
            rng.shuffle(words)
            texts.append(" ".join(words) + ".")
            labels.append(label)
    return texts, np.asarray(labels, np.int32)


def test_tokenize_word2idx_shape():
    ts = TextSet.from_texts(["Hello, World! Hello...", "world again"],
                            [0, 1]).tokenize()
    assert ts.features[0].tokens == ["hello", "world", "hello"]
    ts.word2idx()
    wi = ts.get_word_index()
    # 1-based, frequency-ranked: hello(2) then world(2) then again(1)
    assert set(wi.values()) == {1, 2, 3}
    assert wi["hello"] == 1  # most frequent first
    ts.shape_sequence(5)
    assert all(len(f.indices) == 5 for f in ts.features)
    x, y = ts.to_arrays()
    assert x.shape == (2, 5) and x.dtype == np.int32
    assert y.tolist() == [0, 1]


def test_word2idx_remove_top_and_cap():
    ts = TextSet.from_texts(["a a a b b c d"]).tokenize()
    ts.word2idx(remove_top_n=1, max_words_num=2)
    wi = ts.get_word_index()
    assert "a" not in wi and len(wi) == 2
    # OOV tokens map to 0
    assert ts.features[0].indices[0] == 0


def test_shape_sequence_trunc_modes():
    ts = TextSet.from_texts(["one two three four five"]).tokenize().word2idx()
    pre = [f.indices.copy() for f in
           TextSet.from_texts(["one two three four five"]).tokenize()
           .word2idx(existing_map=ts.get_word_index())
           .shape_sequence(3, trunc_mode="pre").features]
    post = [f.indices.copy() for f in
            TextSet.from_texts(["one two three four five"]).tokenize()
            .word2idx(existing_map=ts.get_word_index())
            .shape_sequence(3, trunc_mode="post").features]
    wi = ts.get_word_index()
    assert pre[0].tolist() == [wi["three"], wi["four"], wi["five"]]
    assert post[0].tolist() == [wi["one"], wi["two"], wi["three"]]


def test_read_folder_and_csv(tmp_path):
    (tmp_path / "pos").mkdir()
    (tmp_path / "neg").mkdir()
    (tmp_path / "pos" / "a.txt").write_text("good great fine")
    (tmp_path / "neg" / "b.txt").write_text("bad awful poor")
    ts = TextSet.read(str(tmp_path))
    assert len(ts) == 2 and ts.label_map == {"neg": 0, "pos": 1}

    csvp = tmp_path / "data.csv"
    csvp.write_text("text,label\nhello world,1\nbye now,0\n")
    ts2 = TextSet.from_csv(str(csvp))
    assert len(ts2) == 2 and ts2.labels.tolist() == [1, 0]


def test_raw_text_to_trained_text_classifier():
    """VERDICT r3 task 5 'done' bar: raw-strings-to-trained-model."""
    init_zoo_context()
    import optax
    from analytics_zoo_tpu.models.textclassification import TextClassifier

    texts, labels = _corpus()
    ts = (TextSet.from_texts(texts, labels).tokenize()
          .word2idx().shape_sequence(12))
    fs = ts.generate_sample()
    vocab = len(ts.get_word_index()) + 1  # + padding id 0
    m = TextClassifier(class_num=2, token_length=16, sequence_length=12,
                       encoder="cnn", encoder_output_dim=32,
                       vocab_size=vocab)
    m.compile(optimizer=optax.adam(0.01), loss="scce", metrics=["accuracy"])
    h = m.fit(fs, batch_size=32, nb_epoch=10)
    assert h["loss"][-1] < h["loss"][0]
    x, y = ts.to_arrays()
    assert m.evaluate(x, y, batch_size=32)["accuracy"] > 0.9


def test_bert_small_classifier_finetune():
    """BERT-small fine-tune from the text pipeline (start of config #4):
    token ids + type ids + position ids + mask -> pooled output -> head."""
    init_zoo_context()
    import optax
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras.engine import Lambda
    from analytics_zoo_tpu.pipeline.api.keras.layers import BERT, Dense

    texts, labels = _corpus(n_per=24)
    seq = 12
    ts = TextSet.from_texts(texts, labels).tokenize().word2idx().shape_sequence(seq)
    x, y = ts.to_arrays()
    n = x.shape[0]
    vocab = len(ts.get_word_index()) + 1
    token_type = np.zeros((n, seq), np.int32)
    position = np.tile(np.arange(seq, dtype=np.int32), (n, 1))
    mask = (x != 0).astype(np.float32)[:, None, None, :]

    ids = Input(shape=(seq,), name="ids")
    tt = Input(shape=(seq,), name="tt")
    pos = Input(shape=(seq,), name="pos")
    am = Input(shape=(1, 1, seq), name="mask")
    seq_and_pooled = BERT(vocab=vocab, hidden_size=32, n_block=2, n_head=2,
                          seq_len=seq, intermediate_size=64,
                          name="bert")([ids, tt, pos, am])
    pooled = Lambda(lambda s, p: p, name="take_pooled")(seq_and_pooled)
    out = Dense(2, activation="softmax", name="cls")(pooled)
    m = Model(input=[ids, tt, pos, am], output=out)
    m.compile(optimizer=optax.adam(1e-3), loss="scce", metrics=["accuracy"])
    h = m.fit([x, token_type, position, mask], y, batch_size=16, nb_epoch=6)
    assert h["loss"][-1] < h["loss"][0]
    res = m.evaluate([x, token_type, position, mask], y, batch_size=16)
    assert res["accuracy"] > 0.75


def test_bucketed_training():
    """Length bucketing (SURVEY §7 hard parts): ragged texts pad to the
    smallest fitting bucket, batches never mix shapes, and a
    length-agnostic model trains across buckets."""
    import numpy as np
    from analytics_zoo_tpu.common.context import init_zoo_context
    from analytics_zoo_tpu.feature import BucketedFeatureSet
    from analytics_zoo_tpu.feature.text import TextSet
    from analytics_zoo_tpu.models.textclassification import TextClassifier

    init_zoo_context()
    rng = np.random.default_rng(0)
    short = ["good fun " * 2, "bad sad " * 2] * 24        # ~4 tokens
    long_ = ["good fun nice day " * 5, "bad sad poor day " * 5] * 24
    texts = short + long_
    labels = np.asarray(([1, 0] * 24) + ([1, 0] * 24), np.int32)

    ts = TextSet.from_texts(texts, labels).tokenize().word2idx()
    fs = ts.to_bucketed([8, 24], seed=1)
    assert isinstance(fs, BucketedFeatureSet)
    assert len(fs) == 96
    shapes = {bx.shape[1] for bx, _ in fs.iter_batches(8, epoch=0)}
    assert shapes == {8, 24}  # batches never mix bucket lengths
    # interleave reshuffles across epochs
    o0 = [bx.shape[1] for bx, _ in fs.iter_batches(8, epoch=0)]
    o1 = [bx.shape[1] for bx, _ in fs.iter_batches(8, epoch=1)]
    assert o0 != o1

    m = TextClassifier(class_num=2, token_length=16, sequence_length=24,
                       encoder="cnn", vocab_size=len(ts.word_index) + 2)
    m.compile(optimizer="adam", loss="scce", metrics=["accuracy"], lr=2e-3)
    h = m.fit(fs, batch_size=8, nb_epoch=6)
    assert h["loss"][-1] < h["loss"][0]


def test_bucketed_guards():
    import numpy as np
    import pytest
    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    from analytics_zoo_tpu.feature.text import TextSet
    from analytics_zoo_tpu.models.textclassification import TextClassifier

    reset_zoo_context()
    init_zoo_context()
    try:
        texts = ["a b c d"] * 16
        labels = np.zeros(16, np.int32)
        ts = TextSet.from_texts(texts, labels).tokenize().word2idx()
        fs = ts.to_bucketed([4, 8])
        assert len(fs.buckets) == 1  # all same length → one non-empty bucket
        assert len(ts.to_bucketed([4, 4, 8]).buckets) == 1  # dup lens dedup
        m = TextClassifier(class_num=2, token_length=8, sequence_length=4,
                           encoder="cnn", vocab_size=10)
        m.compile(optimizer="adam", loss="scce")
        # validation needs one dense array: refused before any step runs
        with pytest.raises(ValueError, match="bucketed validation_data"):
            m.fit(fs, batch_size=8, nb_epoch=1, validation_data=fs)
        assert m.finished_iterations == 0 and m.params is None
    finally:
        reset_zoo_context()
        init_zoo_context()


def test_from_parquet_roundtrip(tmp_path):
    """``readParquet`` parity (``TextSet.scala:372``) via pyarrow."""
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as pq

    from analytics_zoo_tpu.feature.text import TextSet

    path = str(tmp_path / "corpus.parquet")
    table = pa.table({"text": ["good film", "bad film", "fine film"],
                      "label": [1, 0, 1]})
    pq.write_table(table, path)
    ts = TextSet.from_parquet(path)
    assert len(ts) == 3
    assert ts.labels.tolist() == [1, 0, 1]
    arr, y = ts.tokenize().word2idx().shape_sequence(4).to_arrays()
    assert arr.shape == (3, 4)

    with pytest.raises(ValueError, match="no column"):
        TextSet.from_parquet(path, text_col="nope")
