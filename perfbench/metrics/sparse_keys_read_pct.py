"""Keys the sparse layers' selection listed for the decode kernel (the
listed blocks' keys up to the query, counted on the device where the blocks
are chosen and read back with the iteration's tokens) over the keys a dense
layer would have read (every key up to the query):
ServingEngine.stats()["sparse"] keys_read over keys_live, per KV group,
summed over groups, sparse layers and decode iterations, over the engine's
life in the job. It says whether the selection works at all: one that
listed every block, or traffic that stays before ``dense_len``, reads 100%,
and the cell would measure dense attention. A program without the counter
gives None."""
from yardstick.engine_readers import engine_stats


def read(run):
    sparse = engine_stats(run).get("sparse") or {}
    if not sparse.get("keys_live"):
        return None
    return 100.0 * sparse["keys_read"] / sparse["keys_live"]
