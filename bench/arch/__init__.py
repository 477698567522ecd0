"""Architectures, one module each, found by a configuration's ``"arch"``
(``cells.load_arch``).  Everything in ``bench/`` that depends on the
model's blocks comes from the module; the rest of the benchmark is
shared.  A module gives, for a configuration's ``model`` sizes ``dims``:

* ``param_tree(dims)``: the served model's parameter tree as
  ``weights.Leaf``s, each with its stacked leading axes (layer, expert)
  and its initialisation; the layer stacks are top-level entries;
* ``layers(dims)``: ``(stack, index, kind)`` of every layer, in order;
* ``KINDS``: for each kind, the plain float32 forward of one layer,
  ``fn(dims, w, x, group, *args, low, **static) -> (x, dropped)``, with
  every product through ``reference.model.matmul`` (``low`` rounds its
  operands for the control and the witness);
* ``routing(dims, sizes) -> (args, static)``: what every layer takes
  beside the routing group of each token, for groups of ``sizes``
  tokens (an expert layer's capacity rule);
* ``program_sizes(cfg)``: the program's configuration in the keys of the
  file's ``model``, which the harness compares with the file;
* ``prefill_flops(dims, batch, prompt_len)`` and
  ``decode_step_flops(dims, batch, pos)``: useful FLOPs of a call;
* ``dispatch(dims)``, where an exchange runs the daemon's plan: (experts
  a token goes to, experts of a layer, bytes a token sends).
"""
