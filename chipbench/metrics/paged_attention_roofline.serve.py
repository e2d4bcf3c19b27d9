"""Share of its memory roofline that the paged-attention kernel reached
in the window, in %: the bytes its calls had to move (the family's
`paged_attention_bytes`, for dense `families/dense.py`: K and V
of each sequence's context in every layer, bfloat16, and the queries and
outputs) over the chip's HBM
bandwidth (`peaks.json`), against the device time of the kernel's calls
(the Pallas custom call in the trace's `XLA Ops`).  The kernel is bound
by memory, not by its FLOPs (see `families/dense.py`)."""

KERNEL = r'custom_call_target="tpu_custom_call"'
KV_BYTES = ACT_BYTES = 2          # bfloat16 pools, queries and outputs


def read(run):
    seconds = run.trace.kernel_seconds(KERNEL)
    if not seconds or not run.steps or run.peaks is None:
        return None
    moved = sum(run.family.paged_attention_bytes(run.dims, b, ctx,
                                                 KV_BYTES, ACT_BYTES)
                for b, ctx in run.steps)
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / seconds
