"""Model blocks: what the benchmark knows of one kind of model.

A configuration file names its block (``"block": "dense"``, beside
``arch``), and ``spec.block_module`` loads ``blocks/<name>.py``.  The
harness and the metric readers reach the model's weights, its plain
reference and its counted work only through these four functions, so a
model of another block is added as new files:

* ``layout(model)`` → the weight tree of ``(shape, scale)`` leaves that
  ``weights.make`` draws (scale ``None``: a norm gain of ones, else a
  normal draw times ``scale``).  Its flattened order is the drawing
  order, so it fixes every weight's bits for a seed.
* ``served_logits(params, model, padded_prompt, served, *, rank, iters,
  decode_pad, control=False)`` → the plain float32 reference's logits
  ``[len(served), vocab]`` at every served position: prefill of the
  prompt as admitted, the factorization of the K/V the program
  factorizes, and teacher-forced decode.  It imports nothing of the
  program.  ``control=True`` computes the same one precision lower
  (the float8 control).
* ``forward_flops(model, prompt_len)`` → the FLOPs of one prompt's
  prefill forward, at the real length, with the head for the one sampled
  position.  A block whose chip holds a share of the experts counts that
  share.
* ``reorth_needed(model, prompt_len, rank, iters_extra, a_bytes=2)`` →
  (FLOPs, bytes) that the Lanczos re-orthogonalization of one prompt
  needs.  It counts the layers the program factorizes, at the width it
  factorizes them: a block where only some layers hold low-rank K/V (a
  window/full mix) counts those layers only.

The toolkit that any block's reference uses is ``bench/reference.py``;
the chips' peaks and the roofline time are ``bench/counts.py``.
"""
