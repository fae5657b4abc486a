"""Pallas hot-spot kernels, each mapped to the paper stage it serves.

Every kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd
public wrapper in ``ops.py`` that always runs compiled on a TPU backend
and in interpret mode (or, for ``paged_decode``, its oracle) elsewhere.

=================  ======================================================
``lif_encode``     Paper Sec. "spike-based encoding": the fused T-tick
                   integrate-and-fire rate encoder that turns a boundary
                   activation tile into signed int8 spike counts — the
                   learnable sparsifier's forward pass at the die edge.
``count_matmul``   The receiving die's first matmul fused with rate
                   decode: int8 spike counts x fp weights without ever
                   materializing the decoded activations — the "compute
                   on the coded wire" half of the paper's D2D story.
``pack4`` /        4-bit wire packing for spike counts (T <= 15), the
``unpack4``        paper's bytes-on-the-wire accounting made literal:
                   two counts per byte across the die boundary.
``paged_decode``   Serving-side extension of the same boundary ethos:
                   one kernel walks a slot's compacted per-shard page
                   list (gather), runs online-softmax flash decode over
                   K1 >= 1 query positions (decode and speculative
                   verify), and emits the int8-quantized partial +LSE
                   wire the coded cross-shard combine consumes — the
                   attention analog of encode-at-the-boundary, with no
                   dense ``[B, pages * page_size, Hkv, dh]`` gather in
                   HBM: one page of K and V is DMA'd per grid step.
=================  ======================================================
"""
