"""Plain references, one module per architecture, named by each
configuration's ``"reference"`` key. They import nothing of the program.

A reference module gives the harness:

- ``spec(conf)``: its view of the model, a hashable dict built from the
  configuration file's own keys; it has at least ``n_layers`` and
  ``vocab_size``.
- Seeded weights: ``layer_key(seed, l)`` and ``top_key(seed)``, the PRNG
  keys of layer ``l`` and of the embedding, head and final norm;
  ``layer_weights(m, init, seed, l)`` and ``top_weights(m, init, seed)``,
  the weights made from them. The layer index decides the layer's kind.
- The forward pieces, float32 at ``HIGHEST`` precision, each taking
  ``quant`` (None, or the control's precision): ``embed(tokens, top, m)``;
  ``layer(x, w, pos, m, quant)``, one layer on one sequence, of the kind
  its weights are; ``logits(x, top, m, quant)``.
- Model FLOPs: ``token_flops(m, context)`` and ``prefill_flops(m,
  prompt_len)``.

The program's side of an architecture is ``bench/adapters/<reference>.py``.
"""
