"""Plain references, one module per published ``model_type``.

``bench/reference/<model_type>.py`` is found by the ``model_type`` of a
configuration file (``spec.load_reference``) and exports
``spec_for(config, t_max, n_max, capacity, low=False)`` and
``logits(params, prompt, served, spec)``; it may export ``PROGRAM_KEYS``,
published key -> ``ModelConfig`` field, which ``serve.model_config``
applies beside the widths.  What every architecture shares (sizes,
precision and its float8 control, QUOKA selection, attention) is
``common``.  A new architecture is added by adding its module.
"""
