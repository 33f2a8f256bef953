"""Plain float32 references, one module per model family, independent of
the program (nothing here imports ``repro``).

Each module provides:

* ``init(config, key)``: the benchmark's weights, made from the seed in
  one jitted call, in the types they are served in and in the layout the
  program's model takes;
* ``logits(params, config, tokens, read_pos, mode)``: next-token logits
  at ``read_pos`` for each row of ``tokens``, computed layer by layer.
  ``mode="f32"`` is the reference (float32, matmuls at HIGHEST
  precision); ``mode="fp8"`` is the control, the same forward with every
  weight matmul taken in float8 (e4m3, scaled per row of activations and
  per output column of weights).
"""
