"""Architecture modules: one file per ``model_type``, found by name.

``config.arch_for(conf)`` loads ``bench/arch/<model_type>.py``, where
``model_type`` is the configuration file's key of that name (the
published ``config.json`` has it).  Every step of the harness that
depends on the architecture goes through that module, so a configuration
of a new architecture brings ``bench/configs/<name>.json`` and, where the
architecture is new, ``bench/arch/<model_type>.py``, and edits no shared
file.  A module gives:

``program_config(conf) -> ModelConfig``
    The configuration file as the program's ``ModelConfig``.

``reference_model(conf)``
    A frozen (hashable) record of the sizes the plain reference reads;
    it is passed as ``m`` to ``logits_at`` and to ``bench/counts.py``.
    Besides what the module's own forward reads, it carries:

    * ``n_heads``, ``n_kv_heads``, ``head_dim``: query heads, KV heads
      and head size of the layers that read the paged clustered kernel;
    * ``attn_layers``: how many layers read that kernel;
    * ``matmul_params``: weight-matrix parameters one token passes
      through.  For experts, the routed top-k, the shared expert and the
      router count; an expert the token does not visit never does.

``logits_at(params, tokens, events, state_of, out_pos, *, m, mem, weight_quant)``
    The plain float32 forward of one request at ``highest`` matmul
    precision, importing nothing of the program: logits
    ``(len(out_pos), vocab)``.  ``tokens``, ``events``, ``state_of`` and
    ``out_pos`` are ``reference.request_inputs``'s arrays, ``mem`` the
    ``reference.Memory`` of the configuration.  Layers with clustered
    memory state build it with ``reference.cluster_states`` and attend
    with ``reference.attend``; ``weight_quant`` rounds every weight
    matrix with ``reference.fake_quant`` (the control).

``leaf_rules`` (optional)
    ``{leaf name: "ones" | "zeros"}``, applied by ``weights.build`` over
    its own rules (norm scales are ones); every other leaf is drawn.
"""
