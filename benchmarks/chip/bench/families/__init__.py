"""One module per model family, found by a configuration file's
``model_type`` (``bench.model.family``).  A family module knows one block
layout and nothing of cells, traffic or drivers; it provides:

* ``program_config(c)``: the program's ``ModelConfig`` for the file ``c``;
* ``weights(c, key)``: the base weights from one PRNG key, in the
  program's parameter layout and the served type (``bench.model`` jits it
  once per configuration);
* ``Dims`` (``Dims.from_config(c)``, ``weight_bytes``) and
  ``train_step_work(dims, rows, seq, rank, targets)``: operations and bytes
  from shapes, under ``bench.flops``'s conventions;
* ``lora_key(path)`` and ``lora_path(key)``: the path of an adapter in the
  program's tree to and from its key in the ``{key: (A, B, scale)}``
  factors that the family's plain reference (``references/<c["reference"]>
  .py``) takes; ``bench.model.lora_factors`` and ``lora_tree`` map whole
  trees with them.

A new family is one such module plus its reference.
"""
