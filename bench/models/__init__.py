"""One module per published ``model_type``: everything the benchmark knows
of one architecture's block. ``spec.model_module(cfg)`` loads
``bench/models/<cfg["model_type"]>.py`` by path, so a configuration of a new
architecture brings its module as a new file, and no file of ``perfkit/``
names a model.

A module provides:

- ``Shape.from_config(cfg)``: a frozen, hashable shape (a static argument of
  the jitted functions below) with at least ``layers``, ``vocab``,
  ``itemsize`` (bytes an element as served), ``lora_rank`` and
  ``lora_targets``;
- ``program_config(cfg)``: the program's ``ModelConfig`` for the file's
  sizes. This is the one place a module touches the program;
- ``kv_bytes_per_token(s)`` and ``adapter_bytes(s)``: what one token's cache
  and one adapter take in the pool, to size the engine's tiers;
- ``make_params(key, s)``: the weights, drawn on the device in one jitted
  call, as a tree laid out like the engine's own (``weights.check_layout``
  holds it to that); ``make_adapters(key, s, n)``: ``n`` adapters as
  ``{target: (A (n, L, d_in, r), B (n, L, r, d_out))}``;
- ``Consts.from_config(cfg)`` (hashable) and ``logits_at(params, adapter,
  tokens, at, lora_from, *, c, precision)``: the plain reference, one
  sequence at a time, imported from nothing of the program. It gives the
  float32 logits ``(len(at), vocab)`` at positions ``at`` of the causal
  forward over ``tokens``, positions before ``lora_from`` without the
  adapter, at ``precision`` "highest" (the oracle) or a control below it
  (``reference.numerics``);
- ``span_flops(s, start, n, emits_last)``: model FLOPs of ``n`` tokens
  computed at positions ``start ..``, the LM head once if the last emits;
- ``<kernel>_need(s, ...) -> (FLOPs, bytes)`` for each kernel on the
  model's path that has a roofline metric (``ragged_extend_need(s, rows)``
  per layer, ``fused_sgmv_need(s, target, tokens, adapters)``). A module
  without one is for a model whose path runs no such kernel: the trace then
  holds none of it and that kernel's roofline is left out. Where the kernel
  ran all the same, its reader raises.

Work is counted from real token counts and context lengths, never from a
kernel's grid or block sizes, so the yardstick stays put when a kernel
changes."""
