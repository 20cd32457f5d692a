"""Model families the benchmark knows: for each, the weight layout the
program is served with, the plain float32 reference, and the cost model
(required bytes and FLOPs per prefill and per decode step)."""
