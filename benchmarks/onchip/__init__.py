"""On-chip serving benchmark.

    python3 -m benchmarks.onchip.run --workload smollm360m.chat \
        --seed 7 --seconds 30 --trace 0

``BENCHMARK.json`` at the repository root binds a configuration to a
traffic mix as a cell.  Everything else is found by name under this
directory: ``configs/<config>.json`` (sizes and serving knobs),
``references/<config>.py`` (the plain float32 reference, and what else
the configuration brings), ``traffic/<mix>.json`` (the generator's
parameters), ``cells/<cell>.json`` (rate and the correctness limit),
``metrics/<metric>.py`` (one per-layer reader each),
``costs/<kernel>.py`` (a kernel's operations and bytes) and
``peaks.json`` (the chip's peaks by ``device_kind``).

Adding a configuration
----------------------

A configuration comes by adding files and appending entries; no file
that is here changes.  The files, for a configuration ``<config>`` and
each of its cells ``<config>.<mix>``:

* ``configs/<config>.json``: ``name``, ``arch`` (the program's
  ``get_config`` name), ``source``, ``reduced``, ``model`` (fields of the
  program's ``ModelConfig``; a dict value, under ``moe``, ``mamba`` or
  ``mla``, replaces fields of that sub-config), ``serve`` (its
  ``ServeConfig``), ``warm_granule``, and ``sizes``: every size the
  reference and the FLOP count read, each checked against the program's
  config as it runs, a dotted key (``"moe.n_experts"``) against a field
  of a sub-config.  ``mixer`` is not checked: it selects ``flops.py``'s
  count where the reference brings none.  The catalog's numbers, the
  sizes assumed and the deployment the cut stands for go in the same
  file.
* ``references/<config>.py``: ``logits(params, tokens, rows, sizes,
  quant=None)``, the plain float32 reference (``references/common.py``
  has the products, the control's rounding, ``stack``, which walks the
  program's head, repeated pattern and tail layers with a block chosen by
  each layer's kind, and ``head``, tied or untied at a stated logit
  scale).  Optional: ``INIT``, init rules for the leaves that
  ``weights.RULES`` lacks (``weights.py`` lists the kinds of rule), and
  ``prefill_flops(sizes, prompt_len)`` / ``decode_flops(sizes, n_active,
  ctx_tokens)``, the model FLOPs of one prompt and of one decode step,
  which ``mfu.*`` read in place of ``flops.py`` (which knows the ``attn``
  and ``mingru`` mixers only).
* ``cells/<config>.<mix>.json``: ``limits`` (``max_logit_gap``, set from
  the program's and the control's readings, ``calibrate.py``) and, for an
  open loop, ``rate_per_s``.
* a test of the control at a size a CPU test holds, in a test file of its
  own (as ``tests/test_control.py`` does for the configurations here);
* where the cell needs them, a new ``traffic/<mix>.json``, and for a new
  kernel ``costs/<kernel>.py`` and ``metrics/<kernel>_roofline.py``.

``tests/fixtures/`` holds such files for four of the program's smoke
presets (sparse experts, latent attention, Mamba beside attention, Mamba
alone), which ``tests/test_config_files.py`` builds from those files
alone.

The entries appended to ``BENCHMARK.json``: one under ``configs``, one
under ``workloads`` per cell, and each new cell's name in the
``workloads`` list of every metric it reports (the end-to-end metrics of
its traffic, and per-layer metrics such as ``mfu.prefill``,
``idle_share.<mix>``); ``setup_s`` has no list and covers every cell.
"""
