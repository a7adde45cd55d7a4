"""Plain reference implementations, one module per model family, found by
the ``reference`` name in a configuration file.

A family module gives everything the harness needs to know of a model:

* ``Shape``: the sizes of one model, ``Shape.from_config(entry)`` from a
  configuration file's ``model`` or ``draft`` entry; ``shape.vocab`` is
  the vocabulary the traffic draws from;
* ``make_params(shape, seed_words, weights)``: the served weights, made on
  the device from a seed;
* ``rows(params, shape, seq, first, pad_to, ...)``: the reference forward's
  statistics of the logits, which decide ``correct``;
* ``program_config(shape, name)``: the program's ``ModelConfig`` for these
  sizes (importing the program inside the function, so that the reference
  forward imports nothing of it);
* ``dims(shape)``: what the least-work counts read (``counts.ModelDims``).
"""
