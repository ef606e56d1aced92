"""The (query, key) pairs in the tiles the windowed kernels walk over
the pairs the window keeps: 1.0 is a kernel that wastes nothing. From
the program's counters of the rule that picked the tile
(`attn_window_tiles`, `attn_causal_tiles`, `attn_window_layers`: the
step's one trace) and benchmark/flops_mellum2.py's `window_pairs` (the
runner's fact `swa_kept_pairs_per_layer`). At T 16,384 under a window
of 1,024: 2.00 at whole tiles of 1,024, 1.50 at 512, 1.25 at 256."""

from benchmark.layer_metrics import _mellum


def read(run: dict):
    tiles, kept = _mellum.tile_facts(run), run["facts"].get(
        "swa_kept_pairs_per_layer")
    if tiles is None or not kept:
        return None
    tile, walked = tiles
    # the kernels walk one sequence at a time: its tiles, times the batch
    batch = run["facts"]["tokens_per_step"] // run["facts"]["seq"]
    return walked * tile * tile * batch / kept
