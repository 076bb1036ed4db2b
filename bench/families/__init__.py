"""Model families of the benchmark: one file each, found by the name that
a configuration file gives under ``"family"`` (``common.family``).

A family file holds everything that depends on the model's architecture,
in plain ``jax.numpy`` that imports nothing of the program under test
except where it builds the program's own configuration:

* ``Dims`` with ``Dims.from_config(config_file)``: the sizes it reads;
* ``program_config(dims, config_file)``: the program's ``ModelConfig``;
* ``init_weights(dims, seed)``: the seeded weights, as the program lays
  them out, made on the device in one call;
* ``loss(dims, arith, weights, tokens)`` and
  ``logits_at(dims, arith, weights, tokens, positions)``: the plain
  reference's training loss and serving logits;
* ``train_flops_per_token(dims, seq_len)``, ``prefill_flops(dims, n)``
  and ``decode_flops(dims, attended)``: the FLOPs the algorithm requires.

What does not depend on the model (arithmetic, tokens, optimizer, the
data-parallel exchange) is in ``bench.reference``.
"""
