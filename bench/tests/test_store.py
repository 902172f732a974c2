"""Store keys follow the code; runs leave the pristine store untouched."""

import hashlib
import os

from bench import store


def test_key_changes_with_the_program_source(tmp_path, monkeypatch):
    src = tmp_path / "src" / "repro"
    src.mkdir(parents=True)
    (src / "a.py").write_text("x = 1\n")
    monkeypatch.setattr(store, "ROOT", str(tmp_path))
    before = store.code_hash("fig89_numpy")
    assert store.code_hash("fig89_numpy") == before
    (src / "a.py").write_text("x = 2\n")
    assert store.code_hash("fig89_numpy") != before


def _digest(path):
    h = hashlib.sha256()
    for d, _, files in sorted(os.walk(path)):
        for f in sorted(files):
            h.update(f.encode())
            with open(os.path.join(d, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def test_runs_leave_the_pristine_store_byte_identical(small_bench, capsys):
    rc, res, _ = small_bench("numpy.fig89_forward", 11, capsys=capsys)
    assert rc == 0 and res["correct"]
    (key,) = [n for n in os.listdir(store.STORES) if not n.endswith(".json")]
    pristine = os.path.join(store.STORES, key)
    before = _digest(pristine)
    rc, res, _ = small_bench("numpy.fig89_forward", 12, capsys=capsys)
    assert rc == 0 and res["correct"]
    assert _digest(pristine) == before
    # nothing else stays behind: the store, its meta file, and no run dirs
    assert sorted(os.listdir(store.STORES)) == [key, key + ".json"]
