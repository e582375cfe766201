"""One module per algorithm a configuration can name (``"algorithm"`` in
its file). Found by name; nothing lists them, and the harness knows
nothing of what an algorithm computes. A module gives:

``build(config)``
    the aggregation the program serves (``.servable()``), built as a
    user builds it.
``PAYLOAD_KEY``
    the key of a published snapshot's payload that holds the carried
    table (what "ready on the device" waits for, and the final table).
``draw_queries(rng, n, recent_src, recent_dst, config)``
    ``(queries, records)``: ``n`` query objects and an int64 array of
    one row per query, which is all the reference sees of them.
``answer_value(answer)``
    an answer as one integer.
``Reference(config)``
    the plain reference, which imports nothing of the program:
    ``fold(src, dst)`` one window, in order; ``expected(records)`` what
    each recorded query has to answer at the current prefix;
    ``compare_final(table)`` the program's final table held to the
    current prefix, as ``{number: count}`` (each has the limit 0);
    ``table()`` the reference's own state in the form of that table
    (the control publishes it one window stale).
``chip_paths_problem(agg, server)`` (optional)
    a reason why the run did not take the chip's paths, or None.
``make_stream(config, source)`` (optional)
    the stream the server ingests, where it is not the default
    ``SimpleEdgeStream`` of count windows over ``IdentityDict``
    (``cellrun.default_stream``): a mesh context, a record stream.
``fold_shape(config, src, dst)`` (optional)
    the shapes one window gives a byte model (``lib/bytes_model.py``).
``table_rows(config)`` (optional)
    rows of the carried table, for a reckoning of memory.
"""
