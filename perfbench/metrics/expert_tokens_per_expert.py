"""Tokens one expert's products see in one decode iteration: the (token,
choice) pairs the decode program counted on the held experts,
``ServingEngine.stats()["experts"]["decode_pairs"]``, over decode iterations
(``decode_iterations`` x the window's steps) x expert layers x held experts,
over the engine's life in the job. 16 with 128 slots decoding, 4 experts a
token and all 32 held: the load a deployment gives an expert, and what tells
this cell's grouped products from a cell that holds a share of the experts
(2 a call). A program without the counter gives None."""
from yardstick.engine_readers import engine_stats


def read(run):
    stats, model = engine_stats(run), run["cell"].model
    experts = stats.get("experts") or {}
    steps = (stats.get("decode_iterations") or 0) * int(
        run["job"].get("decode_window") or 1)
    calls = (steps * len(model.layers_of(run["config"], mlp="moe"))
             * len(experts.get("pairs_per_expert") or ()))
    if "decode_pairs" not in experts or calls <= 0:
        return None
    return experts["decode_pairs"] / calls
