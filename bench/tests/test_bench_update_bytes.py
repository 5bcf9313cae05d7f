"""The UPDATE bytes function against counts made by hand."""
from bench.harness.update_bytes import additions_bytes, update_batch_bytes


def test_one_path_home_first():
    # B=1, L=2, W=1, C=1, H+1=2, S=3:
    # reads  objects 8 + length 4 + budget 4 + homes 8 + costs 8
    #        + words 8 + table 1*2 + load 12          = 54
    # writes cost 4 + flags 2 + chosen 2*2 + first/server 2*2*4 + load 12
    #                                                 = 38
    assert update_batch_bytes(1, 2, 1, 1, 2, 3, gate=False) == 54 + 38


def test_gate_adds_a_second_read_of_words_and_homes():
    plain = update_batch_bytes(256, 6, 1, 5, 6, 6, gate=False)
    gated = update_batch_bytes(256, 6, 1, 5, 6, 6, gate=True)
    # words 256*6*4 + homes 256*6*4 + routed latency written and read
    assert gated - plain == 256 * 6 * 4 * 2 + 256 * 4 * 2


def test_linear_in_paths_but_for_the_load():
    a = update_batch_bytes(128, 6, 2, 10, 6, 40, gate=True)
    b = update_batch_bytes(256, 6, 2, 10, 6, 40, gate=True)
    assert b - a == a - 2 * 40 * 4


def test_additions():
    assert additions_bytes(10) == 80
