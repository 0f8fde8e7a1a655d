"""Pallas TPU kernels for the framework's compute hot-spots, each with a
jitted ops.py wrapper and a pure-jnp ref.py oracle (validated in interpret
mode on CPU; see tests/test_kernels_*.py):

- quantize/:   fused stochastic quantize-dequantize (paper Eq. 12 wire
               round trip, optionally plus the receiver's base) -- the one
               kernel QDFedRW's round runs for every hop and aggregation
               payload; ref.py replays its counter-hash uniforms.
- rowmerge/:   the protocol round's row writes into the (n, d_pad) client
               matrix, in place, one pass of whole (8, 128) tiles per
               group of rows that holds a changed one.
- ssd_scan/:   Mamba2 SSD chunked scan (sequential-grid VMEM state) -- the
               SSM archs' training hot-spot.
- block_attn/: blockwise flash-style causal attention (never materializes
               the L x L score tensor) -- targets the §Roofline prefill
               memory term.
"""
