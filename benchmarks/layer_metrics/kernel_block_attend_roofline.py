"""Kernels: the paged attend's share of its roofline in the traced
decode chunk of a family that generates by diffusion over blocks: the
call the token step makes (``paged_flash_decode_attend``) with a block's
``block x n_rep`` query rows a K/V head folded into one position's
heads, since every row of a block sees the same keys. Time: summed
device time, inside the traced window, of the ``tpu_custom_call`` events
of that name whose result is ``[slots, K/V heads, block x n_rep, head]``.
Work (``flops_sdar.block_attend_work``): the live slots' pages read once
a forward (the program's own count for the traced chunk,
``ServingMetrics.block_by_chunk``: a slot reads the pages below its
position at the chunk's start in every forward of each block that can
still deliver) and the block's own rows; a page held by several slots
would be counted once a slot (this mix shares none). Memory bounds it.
The stage's rows of the chunk's earlier blocks, which the kernel also
folds, are not counted: the share reads a little low, never high.
Returns nothing when the program has no such counters or when the
kernel's calls in the trace are not ``chunks x blocks x (denoising steps
+ 1) x layers``."""
from benchmarks import flops, flops_sdar, trace_reduce
from benchmarks.entries.serve_paged_greedy_sdar import (forwards,
                                                        traced_chunks)


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    chunks = traced_chunks(run, "block_by_chunk")
    if not chunks:
        return None
    g = c["generation"]
    n_rep = c["num_attention_heads"] // c["num_key_value_heads"]
    kernel = ("tpu_custom_call", "paged_flash_decode_attend",
              f" = bf16[{s['n_slots']},{c['num_key_value_heads']},"
              f"{g['block_length'] * n_rep},{c['head_dim']}]")
    seconds = trace_reduce.op_seconds(run["reduced"], *kernel)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *kernel)
    if seconds <= 0 or calls != (forwards(c, len(chunks))
                                 * c["num_hidden_layers"]):
        return None
    # (denoising forwards, storing forwards, positions, delivered, kept,
    # dead, pages one layer's attends walked) a chunk
    live_blocks = sum(ch[3] + ch[4] for ch in chunks) // g["block_length"]
    ops, nbytes = flops_sdar.block_attend_work(
        c, sum(ch[6] for ch in chunks),
        live_blocks * (g["denoising_steps"] + 1))
    n = c["num_hidden_layers"]
    return flops.roofline_share(n * ops, n * nbytes, seconds,
                                run["peaks"])[0]
