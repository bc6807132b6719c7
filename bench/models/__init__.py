"""Model plug-ins: one file per architecture, chosen by a configuration's
``model.arch`` (``plugins.model``). A new client model needs only its file
here, its configuration and its cell; nothing else of the benchmark is
edited.

A plug-in is a module with these functions of the configuration's
``model`` section (a dict, hashable as a sorted tuple of its items where
the reference jits over it):

* ``program_config(model)``: the keyword arguments that select the model
  in ``repro.core.FedS3AConfig`` -- ``{"cnn": CNNConfig(...)}`` for the
  paper CNN, ``{"model": ModelConfig(...)}`` for a config-zoo LM. It may
  import the program (``program.ensure_src`` has run); nothing else here
  may.
* ``init_params(model, key)``: the plain reference's initial weights, a
  flat dict {leaf name: array} whose names are those of the program's
  ``global_params``; the flat vector is the leaves in sorted-name order.
* ``forward(model, params, x, drop_key=None)``: the plain reference's
  forward pass, (B, features) -> logits (B, classes), dropout when
  ``drop_key`` is given. Plain ``jax.numpy``, independent of the program.
* ``leaf_sizes(model)``: [(leaf name, size)] in the flat vector's order.
* ``param_count(model)``, ``forward_flops(model)``, ``train_flops(model)``:
  weights, and the FLOPs of one sample's forward and forward-plus-backward
  pass, counted from shapes (a multiply-add is two FLOPs).
* ``histogram_flops(model)``: FLOPs per sample of the pseudo-label
  histogram the upload stage computes.
* ``small(model)``: the same architecture at a size a CPU test holds.
"""
