"""Plain jax.numpy model families: the benchmark's own references.

Nothing here imports mxnet_tpu.  A family module makes seeded weights on the
device, runs the forward pass in float32 at ``highest`` matmul precision (or,
for the control, in a stated lower precision), and for training follows the
optimizer's first steps.
"""
