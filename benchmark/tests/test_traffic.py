"""Each traffic generator makes the same inputs from the same seed."""
import numpy as np

from benchmark.traffic import resident_fit, stream_epochs

BIG_SEED = 2 ** 31 + 12345


def test_resident_fit_data_is_a_function_of_the_seed():
    x1, y1 = resident_fit.make_data(BIG_SEED, 512, 28)
    x2, y2 = resident_fit.make_data(BIG_SEED, 512, 28)
    x3, _ = resident_fit.make_data(BIG_SEED + 1, 512, 28)
    assert np.array_equal(np.asarray(x1), np.asarray(x2))
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    assert 0.2 < float(np.mean(np.asarray(y1))) < 0.8


def test_bin_codes_are_the_binners_own():
    from dmlc_core_tpu.models import QuantileBinner
    x, _ = resident_fit.make_data(5, 2048, 28)
    x = np.asarray(x).copy()
    x[::97, 3] = np.nan
    for missing_aware in (True, False):
        data = x if missing_aware else np.nan_to_num(x)
        binner = QuantileBinner(num_bins=64, missing_aware=missing_aware)
        want = np.asarray(binner.fit_transform(data))
        got = np.asarray(resident_fit.bin_codes(
            data, binner.cuts, missing_aware))
        assert np.array_equal(got, want)


def test_field_vocabulary_fills_the_table():
    for features, fields in ((1 << 20, 39), (4096, 39)):
        vocab = stream_epochs.field_vocabulary(features, fields)
        assert len(vocab) == fields and vocab.sum() == features
        assert vocab.min() >= 2


def test_libfm_file_is_a_function_of_the_seed(tmp_path):
    files = []
    for i, seed in enumerate((BIG_SEED, BIG_SEED, BIG_SEED + 1)):
        label, index = stream_epochs.draw_rows(seed, 300, 4096, 39, 0.26)
        assert index.shape == (300, 39) and index.min() >= 0
        assert index.max() < 4096
        path = tmp_path / f"{i}.libfm"
        size = stream_epochs.write_libfm(path, label, index, 4096)
        assert size == stream_epochs.libfm_bytes(300, 39, 4096)
        files.append(path.read_bytes())
        assert len(files[-1]) == size
    assert files[0] == files[1] and files[0] != files[2]
    first = files[0].split(b"\n")[0].split()
    label, index = stream_epochs.draw_rows(BIG_SEED, 300, 4096, 39, 0.26)
    assert int(first[0]) == label[0] and len(first) == 40
    assert [tuple(map(int, e.split(b":"))) for e in first[1:]] == [
        (f, int(index[0, f]), 1) for f in range(39)]


def test_skewed_ids_a_few_features_take_most_rows():
    _, index = stream_epochs.draw_rows(7, 20000, 1 << 20, 39, 0.26)
    counts = np.bincount(index[:, 20])
    top = np.sort(counts)[::-1]
    assert top[:10].sum() > 0.2 * 20000      # Zipf(1.1), not uniform


def test_expected_tally_counts_partial_epochs():
    label = np.array([1, 0, 1, 1, 0, 0, 1, 0], np.uint8)
    index = np.arange(16, dtype=np.int32).reshape(8, 2)
    want = stream_epochs.expected_tally(label, index, 2, 6)  # 1.5 epochs
    assert want["rows"] == 12 and want["entries"] == 24
    assert want["positives"] == 4 + 1 + 2      # an epoch, then batches 0 and 1
    per_batch = index.reshape(4, -1).sum(axis=1)
    assert want["ids"] == int(per_batch.sum() + per_batch[:2].sum())
    assert want["ids_by_place"] == int(
        (per_batch * [1, 2, 3, 4]).sum() + per_batch[0] + 2 * per_batch[1])


def test_the_seeds_file_is_written_once_and_found_again(tmp_path):
    """A second run of a seed in the same checkout reads the file the first
    one wrote; a file of another size (a run that was killed) is replaced."""
    from pathlib import Path

    from benchmark import harness
    here = Path(__file__).resolve().parents[1]
    cell = harness.load_cell(here, "criteo-ffm.stream-train", BIG_SEED,
                             rehearse=True)
    cell.cache_dir = tmp_path
    path = tmp_path / "train.libfm"
    stamps = []
    for damage in (False, False, True):
        if damage:
            path.write_bytes(b"1 0:1:1\n")
        state = stream_epochs.setup(cell, harness.Spans())
        stream_epochs.teardown(state)
        stamps.append((path.stat().st_mtime_ns, path.read_bytes()))
    assert stamps[0] == stamps[1]
    assert stamps[2][1] == stamps[0][1] and stamps[2][0] != stamps[0][0]
