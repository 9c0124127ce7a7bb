"""Model families: one module for each architecture the benchmark runs.

A configuration file names its family under ``"model"``, as it names its
reference under ``"reference"``; ``bench/run.py`` (``family``) loads
``bench/models/<model>.py`` and reads nothing of the architecture except
through it.  So a configuration of a new architecture brings a module of
its own and a reference of its own, and edits no file of the harness.
A family module defines:

  ``dims(config)``
      the sizes and flags that the reference, the weights and the work
      counts read, from the configuration file (a dict of plain values;
      lists are allowed), ``vocab_size`` among them;
  ``program_config(config)``
      the program's configuration that serves the configuration file,
      every key it cuts (``reduced``) at the served value;
  ``differences(cfg, config)``
      where the program's configuration ``cfg`` does not serve the sizes
      the configuration file states, one line each (empty when it does);
  ``make(dims, seed, dtype=bfloat16)``
      the weights, a flat dict of arrays drawn on the device from the
      seed in one jitted call (``bench.weights.draw``): what the
      reference reads;
  ``to_program(w, dims)``
      the same arrays in the program's parameter tree, which the harness
      checks against the program's own layout;
  ``step_gemms(dims, m, unembed_rows)``
      the dense matmuls (the ``gemm`` kernel family) of one forward over
      ``m`` rows, as ``(M, K, N)`` shapes;
  ``attention_layers(dims)``
      how many layers run paged decode attention (``flash_decode``);
  ``decode_attention(dims, contexts)``
      (operations, bytes) of one such layer's decode over live slots
      whose caches hold ``contexts`` tokens;
  ``prefill_flops(dims, length)``, ``decode_flops(dims, contexts)``
      the model operations of one prefill and of one decode step.

The counts are of the work the model needs as published (live rows,
live cache tokens, causal halves), never what an implementation pads.
"""
