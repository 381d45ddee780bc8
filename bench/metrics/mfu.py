"""mfu (%): operations the model needs per token (bench/benchkit/flops.py:
forward and backward, attention included; remat, padded key/value blocks,
vocabulary padding and the one-hot embedding excluded), times the traced
run's tokens per second, over the cell's chips times the chip's bf16 peak
(layer: train step)."""


def read(ctx):
    seq = ctx.cell.traffic["seq_len"]
    need = ctx.flops.train_flops_per_token(ctx.cell.config["model"], seq)
    return 100.0 * need * ctx.tokens_per_s / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
