"""The least time the chip could take for the routed experts' products of
the traced sub-window, over the traced time of the grouped matrix product
(``_moe_gmm_kernel``), counted for the share held here.  Operations: every
token materialised through the held experts it chose, half an expert a
layer in expectation (8 held of 128, 8 chosen).  Bytes: all 8 held experts'
weights a chunk launch, and at least what one token chooses of them a
decode or verify launch (``ctx["ref"]``).  The larger of operations over
peak FLOP/s and bytes over peak bytes/s: the arithmetic of
``moe_gmm_roofline.longdoc``, over this configuration's own counts."""
import os

from benchmarks.harness import spec

read = spec.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "moe_gmm_roofline.longdoc.py")).read
