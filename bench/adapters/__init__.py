"""The program's side of each architecture, one module per architecture,
named by each configuration's ``"reference"`` key, as its plain reference
(``bench/reference/<reference>.py``) is. An adapter module gives
``bench/program.py``:

- ``check(cfg, m)``: where the program's ``ModelConfig`` departs from the
  reference's spec, as ``{name: (program, file)}``; empty when it runs
  the architecture as the file states it.
- ``build(m, init, layer_keys, top_key, W)``: the program's parameter tree
  made from the reference's seeded weights (``layer_keys`` stacks every
  layer's key), traced inside one jitted call; ``W`` is the number of
  expert shards.
"""
