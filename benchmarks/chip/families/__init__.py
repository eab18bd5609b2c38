"""What the harness knows about one kind of model, one file per family.

A configuration file (``configs/<config>.json``) names its family under
``"family"``; the harness loads ``families/<family>.py`` from the
benchmark's directory, by that name, and calls nothing model-specific
but what it provides:

- ``check(model)``: raise ``ValueError`` for a configuration file the
  family does not model (a key it would not read, a missing one, a value
  it cannot serve). Called when a cell is resolved.
- ``arch_overrides(model) -> dict``: the program's ``ArchConfig``
  attributes the file sets, by name. The harness sets those that are
  dataclass fields on the registry entry the file's ``arch`` names, and
  refuses the cell unless every attribute, fields and properties alike
  (``resolved_head_dim``), reads back as given.
- ``make_weights(model, seed)``: the parameter tree the program serves,
  as the program lays it out, drawn on the device from ``seed``.
- ``served_gaps(model, weights, seqs, length, precision) -> ndarray``:
  the plain reference, which imports nothing of the program. For each
  served position of ``seqs`` (``(prompt, served)`` pairs of token ids,
  rows padded to ``length``), the best reference logit minus the
  reference logit of the served token; for a precision in ``CONTROLS``,
  of the token that control puts first.
- ``CONTROLS``: the names of the lower-precision controls
  ``served_gaps`` takes besides ``"float32"``.
- ``model_ops(model, prefill, decode) -> (int8 ops, bf16 ops)``: the
  model's own work for prefill chunks ``(lo, hi, last)`` (``last``: the
  chunk ends its prompt) and decode tokens given by their position, split
  by the peak each class is held against.
- ``decode_attention_work(model, positions) -> (ops, bytes)``: the decode
  attention of tokens at ``positions``, over every layer.

A family for a new kind of model is a new file here; no other file of
the harness changes.
"""
