"""One module per stream generator a configuration can name
(``"generator"`` in its file). Found by name; nothing lists them.

``edges(config, n_edges, seed, warm_edges)``
    the run's edge columns from the seed: host int32 ``(src, dst)``.
``closing_edges(config, seed)`` (optional)
    ``(src, dst)`` of whole windows, or None: handed out once the
    measured window has closed, compared and never timed.
"""
