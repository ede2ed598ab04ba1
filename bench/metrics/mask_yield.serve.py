"""Share of the mask elements read back that are hits: Σ ``n_hits`` of the
``serve.unpack`` spans / Σ ``mask_elems`` of the ``serve.readback`` spans.
The useful bytes over the bytes the serve stage reads back."""
from bench import program_spans


def read(run):
    got = program_spans.per_op(run, "bench.query_batch")
    if got is None:
        return None
    spans, batches = got
    hits, n = spans.total("serve.unpack", "n_hits", batches)
    elems, m = spans.total("serve.readback", "mask_elems", batches)
    return hits / elems if n and m and elems > 0 else None
