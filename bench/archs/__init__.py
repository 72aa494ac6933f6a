"""One module per served-model architecture, found by a configuration's ``arch`` key.

A model group of a configuration file (``bench/configs/<name>.json``) names
its module with ``"arch": "<module>"``; ``bench.models.load_config`` imports
``bench.archs.<module>`` by that name, so a new architecture is a new file
here and nothing else.  Each module holds everything specific to its kind:

* ``dims(group)``: a frozen, hashable spec of the group's sizes, with at
  least ``name`` and ``vocab``; the spec's class lives in the module, which
  is how the kind-independent code finds the module again
  (``bench.models.arch``);
* ``model_config(dims)``: the program's ``ModelConfig`` (bf16, as served);
* ``param_layout(dims)``: nested ``{name: (shape, law, std)}`` in the
  program's parameter layout, ``"blocks"`` one stacked entry per position of
  the layer pattern (layers axis first) and ``"tail"`` the unstacked rest;
  ``param_count(dims)``;
* ``logits(dims, params, tokens, quant=False)``: the plain float32 reference
  at highest precision, and with ``quant=True`` its float8 control;
* ``forward_flops(dims, rows, prompt_len, new_tokens)`` and
  ``decode_step_cost(dims, batch, context) -> (ops, bytes)``;
* ``GAP_LIMIT``: the widest normalized served-token gap ``correct`` allows,
  with the readings it was set from.
"""
