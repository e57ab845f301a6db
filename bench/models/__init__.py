"""Model modules: one file per family of configurations.

A configuration file names its module with the optional key
``"bench_model"`` (``bench/models/<name>.py``); one that names none uses
``dense_vlm``.  A model module supplies:

* ``sizes(config)``: the configuration's sizes as the benchmark's own
  code reads them, as a hashable value (it keys jit caches);
* ``init_params(sizes, key)``: the seeded weights in the program's
  parameter tree, made on the device in one jitted call;
* the plain reference: ``train_reference`` and ``serve_logits`` and the
  per-layer walk they use.  It imports nothing of the program;
* the operation counts the metric readers need: ``token_weights``,
  ``prefill_flops``, ``decode_flops`` (at a context), ``paged_decode``
  (one decoded token's attention reads, all layers) and
  ``train_step_flops``;
* optionally ``arch_config(config)``: the program's configuration, where
  ``spec.arch_config``'s key-by-key mapping does not fit the family.

A module whose configurations a ``pipeline`` cell runs
(``bench/harness/pipeline_cell.py``) also supplies:

* ``pipeline_params(sizes, key, n_stages)``: the seeded weights in the
  program's stage-stacked pipeline tree, traceable (the driver jits it
  with the mesh's shardings);
* ``pipeline_train_reference(sizes, key, batches, opt, n_steps,
  row_block, n_stages, lp=False)``: the plain reference of the
  pipeline's training job over ``batches`` of text rows (``tokens``,
  ``labels``), returning what ``train_reference`` returns, with leaf
  names of the ``pipeline_params`` tree.

Drivers and readers reach weights, reference and counts only through
the cell's model module (``Run.model``), so a new family comes as new
files.
"""
