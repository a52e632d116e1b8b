"""Binary token-file reader (data/token_reader.py): the LM-native
data path — memory-mapped fixed windows, exact sharding, e2e through
the managed master with the flagship LM."""

import os
import subprocess
import sys

import numpy as np
import pytest

from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.data.token_reader import (
    TokenFileDataReader,
    write_token_file,
)


def _make_file(path, n_tokens, vocab=500, dtype=np.uint16):
    rng = np.random.RandomState(0)
    toks = rng.randint(0, vocab, size=n_tokens)
    write_token_file(path, toks, dtype=dtype)
    return toks


def test_windows_shards_and_contents(tmp_path):
    path = str(tmp_path / "train.bin")
    toks = _make_file(path, n_tokens=16 * 10 + 7)  # trailing partial
    reader = TokenFileDataReader(path, seq_len=16, records_per_shard=4)
    shards = reader.create_shards()
    # 10 full windows (partial dropped) in shards of 4/4/2
    assert [(s[1], s[2]) for s in shards] == [(0, 4), (4, 8), (8, 10)]

    class T:
        class shard:
            start, end = 4, 8
            record_indices = None

    got = list(reader.read_records(T))
    assert len(got) == 4
    for k, (rec,) in enumerate(got):
        assert rec.dtype == np.int32
        np.testing.assert_array_equal(
            rec, toks[(4 + k) * 16:(5 + k) * 16])


def test_append_and_dtype_guard(tmp_path):
    path = str(tmp_path / "t.bin")
    write_token_file(path, [1, 2, 3])
    write_token_file(path, [4, 5])  # append
    reader = TokenFileDataReader(path, seq_len=5)
    assert reader.create_shards() == [(path, 0, 1)]
    with pytest.raises(ValueError):
        write_token_file(path, [70000])  # > uint16


def test_factory_origin(tmp_path):
    path = str(tmp_path / "d.bin")
    _make_file(path, 64, dtype=np.uint32)
    reader = create_data_reader("tokens:%s:8:uint32" % path,
                                records_per_shard=4)
    assert isinstance(reader, TokenFileDataReader)
    assert reader.create_shards() == [(path, 0, 4), (path, 4, 8)]
    with pytest.raises(ValueError):
        create_data_reader("tokens:%s" % path)


@pytest.mark.slow
def test_managed_lm_training_from_token_file(tmp_path):
    """e2e: tokenize -> write_token_file -> managed LM training job
    through the master CLI (the GPT-style pretraining loop)."""
    path = str(tmp_path / "corpus.bin")
    _make_file(path, n_tokens=16 * 256, vocab=128)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.master.main",
            "--model_zoo", "transformer",
            "--model_params",
            "vocab_size=128;dim=32;num_heads=4;num_layers=2;"
            "seq_len=16;dtype=float32",
            "--data_origin", "tokens:%s:16" % path,
            "--batch_size", "16", "--num_workers", "1",
            "--num_minibatches_per_task", "4",
            "--shuffle", "true",  # record_indices through the REAL
            # task manager, not just the unit-test fake
        ],
        capture_output=True, text=True, env=env, timeout=300,
    )
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-3000:]
    assert "job finished" in text
    assert "'failed': {0: 0" in text, text[-2000:]


def test_reader_honors_shuffle_permutation(tmp_path):
    """record_indices (the task manager's shuffle permutation) must
    drive the read order — not the linear range (advisor catch)."""
    path = str(tmp_path / "s.bin")
    toks = _make_file(path, n_tokens=16 * 6)
    reader = TokenFileDataReader(path, seq_len=16, records_per_shard=6)

    class Shard:
        start, end = 0, 6
        record_indices = [5, 2, 0]

    class T:
        shard = Shard

    got = [rec for (rec,) in reader.read_records(T)]
    assert len(got) == 3
    for rec, idx in zip(got, [5, 2, 0]):
        np.testing.assert_array_equal(
            rec, toks[idx * 16:(idx + 1) * 16])
    write_token_file(path, [])  # empty append is a no-op
    assert os.path.getsize(path) == 16 * 6 * 2


def test_dtype_sidecar_guards_appends_and_reads(tmp_path):
    """Headerless format + mixed dtypes would silently corrupt: the
    .meta sidecar records the creation dtype, mismatched appends and
    readers fail loudly, and the factory rejects non-integer dtypes."""
    path = str(tmp_path / "m.bin")
    write_token_file(path, [1, 2, 3, 4])  # uint16 recorded
    with pytest.raises(ValueError, match="would corrupt"):
        write_token_file(path, [5], dtype=np.uint32)
    with pytest.raises(ValueError, match="sidecar"):
        TokenFileDataReader(path, seq_len=2, dtype=np.uint32)
    with pytest.raises(ValueError, match="uint16 or uint32"):
        create_data_reader("tokens:%s:2:float32" % path)
    # matching dtype still appends fine
    write_token_file(path, [5, 6])
    assert TokenFileDataReader(path, seq_len=2).create_shards() == [
        (path, 0, 3)]


def test_truncated_or_stale_shard_fails_loudly(tmp_path):
    """A shard range beyond the file's real length must raise a clear
    error, not silently yield short windows that break the static
    [B, T] batch shape downstream (ADVICE r5 low)."""
    path = str(tmp_path / "trunc.bin")
    _make_file(path, n_tokens=16 * 10)
    reader = TokenFileDataReader(path, seq_len=16, records_per_shard=4)
    # Warm the mmap on the full file, then truncate it underneath the
    # reader — the stale-shard / truncated-file scenario.
    class T:
        class shard:
            start, end = 8, 10
            record_indices = None

    assert len(list(reader.read_records(T))) == 2
    with open(path, "r+b") as f:
        f.truncate(16 * 9 * 2)  # drop the last uint16 window
    reader2 = TokenFileDataReader(path, seq_len=16, records_per_shard=4)
    with pytest.raises(ValueError, match="truncated|stale"):
        list(reader2.read_records(T))


def test_shuffle_indices_out_of_range_fail_loudly(tmp_path):
    """Stale resume metadata (record_indices from a longer file) hits
    the same bounds check."""
    path = str(tmp_path / "stale.bin")
    _make_file(path, n_tokens=16 * 4)
    reader = TokenFileDataReader(path, seq_len=16)

    class T:
        class shard:
            start, end = 0, 2
            record_indices = [1, 99]  # 99 is beyond the 4 windows

    with pytest.raises(ValueError, match="out of range"):
        list(reader.read_records(T))
