"""``_decode_kernel`` time against the live keys and values its launches had to read."""
from benchmarks.harness.readers import decode_attn_roofline as read
