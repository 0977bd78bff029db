"""The FLOPs a trained token needs, by the cell's model module, x
tokens/s/chip over the chip's bf16 peak. Recomputed operations are not
counted."""
from yardstick.readers import peaks_of, train_tokens_per_s_per_chip


def read(run):
    rate = train_tokens_per_s_per_chip(run)
    if rate <= 0:
        return None
    flops = run["cell"].model.train_flops_per_token(run["config"],
                                                    run["job"]["seq"])
    return 100.0 * flops * rate / peaks_of(run)["flops_bf16"]
