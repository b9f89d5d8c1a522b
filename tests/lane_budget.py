"""Brute-force reference for the fused layout's lane-budget windows."""

import numpy as np

from repro.serving.engine import window_lanes


def lane_budget_windows(server, list_id: int) -> list[list[int]]:
    """The C entries of each fused window over one driving list, packed
    greedily entry by entry: an entry opens a new window when its postings
    would overflow the window's ``window_lanes(max_phrase)`` lanes."""
    cq = window_lanes(server.max_phrase)
    c_off = np.asarray(server.arrays["c_offsets"])
    c_len = np.asarray(server.arrays["c_len"])
    windows, used = [[]], 0
    for row in range(c_off[list_id], c_off[list_id + 1]):
        n = int(c_len[row])
        if used + n > cq:
            windows.append([])
            used = 0
        windows[-1].append(row)
        used += n
    return windows
