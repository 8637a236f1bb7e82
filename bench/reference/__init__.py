"""Plain float32 references for the benchmark's correctness check. They
import nothing of the program under test.

A configuration file names its reference as ``reference.module`` (``gqa``
where it names none); the harness loads ``bench/reference/<module>.py`` by
path when it resolves the cell, and refuses the cell before any weights
are made where the file is missing or lacks one of these names:

- ``Dims.from_config(config) -> Dims``: the sizes the reference needs,
  read from the configuration file as published (a hashable value: the
  module may pass it to ``jax.jit`` as a static argument);
- ``make_weights(m, key) -> weights``: the served model's weights rebuilt
  from the seed's key, by the recipe the program initialises them with,
  so that nothing passes from the program to the reference;
- ``logits_at(m, w, seq, at, quant=None) -> np.ndarray``: float32 logits
  ``[len(at), vocab]`` of the one token sequence ``seq`` at the positions
  ``at``. ``quant="fp8"`` computes the control, the same forward in the
  precision below the one the configuration serves in.

The harness calls only these three names, once the window has closed and
the program's state is freed. A new family's reference is one new file
here and one key in its configuration file.
"""
