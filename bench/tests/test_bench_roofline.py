"""The byte counts behind the roofline shares, against hand counts, and the
peaks table."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline  # noqa: E402


@pytest.fixture
def index():
    # two lists: "x" of 3 postings in 2 C entries, "y" of 7 postings in 3
    store = SimpleNamespace(c_offsets=np.array([0, 2, 5]), lengths=np.array([3, 7]))
    ids = {"x": 0, "y": 1}
    return SimpleNamespace(store=store, lookup=ids.get)


def test_list_bytes_by_hand(index):
    # 2 entries x (anchor, pointer, length) x 4 B + 3 lanes x 4 B
    assert roofline.list_bytes(index.store, 0) == 2 * 12 + 3 * 4 == 36
    assert roofline.list_bytes(index.store, 1) == 3 * 12 + 7 * 4 == 64


def test_sweep_bytes_by_hand(index):
    # query 1 reads both lists and writes 2 hits; query 2 reads "y" and an
    # unknown term (nothing), and writes none
    got = roofline.sweep_bytes(index, [["x", "y"], ["y", "zz"]], [2, 0])
    assert got == (36 + 64 + 2 * 4) + 64 == 172


def test_peaks_are_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("cpu")
