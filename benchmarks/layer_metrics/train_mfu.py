"""Step programs: model FLOP/s utilization — the operations forward and
backward REQUIRE per token (6 per matmul parameter + attention;
recomputation not counted) times tokens per second at the median step
time, over the chip's published bf16 peak."""
import statistics

from benchmarks import flops


def read(run):
    t = run["traffic"]
    step_s = statistics.median(run["group_s"]) / run["group_steps"]
    need = flops.train_flops_per_token(run["config"], t["seq"])
    return (100.0 * need * t["rows_per_step"] * t["seq"] / step_s
            / run["peaks"]["bf16_flops_per_s"])
