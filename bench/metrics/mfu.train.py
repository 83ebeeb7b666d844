"""Model FLOPs utilisation of training: model FLOPs per token (bench/flops.py,
no recomputation counted) times tokens per second, over chips times the
chip's peak.  Read from the window's own counts."""


def read(record, trace, peaks):
    if "flops_per_token" not in record:
        return None
    rate = record["tokens"] / record["window_s"]
    return 100.0 * rate * record["flops_per_token"] / (
        record["chips"] * peaks["bf16_flop_s"])
