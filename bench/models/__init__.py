"""One module per model, found by the configuration's ``model`` with
``-`` read as ``_`` (``paper-cnn`` -> ``models/paper_cnn.py``), as
``metrics/mfu.py`` finds ``counts/train_flops_<model>.py``.

A model module supplies, by these names:

* inputs, from the sub-seeds that ``gen.make_inputs`` hands it:
  ``make_data(cfg, seed) -> (x, y, strata)``, the ``n_train + n_test``
  samples (training first) and, per sample, what ``gen.partition``
  splits among the users (a class label);
  ``init_params(cfg, seed)``, the initial parameter pytree in float32,
  made on the device in one jitted call;
* the program's side: ``engine_inputs(cfg, inputs) -> (train, test,
  spec)``, the engine's training and test sets and its ``ModelSpec``,
  with the program's own loss and accuracy;
* the reference's side, importing nothing of the program:
  ``loss(params, x, y, mm)``, the float32 loss of a minibatch with
  every matrix product through ``mm`` (``reference.matmul``), and
  ``FOLD_BLOCK``, how many users the reference trains, quantizes and
  folds at a time, in user order (None: all K in one sum).
"""
