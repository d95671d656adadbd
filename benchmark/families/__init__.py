"""One module per model family, found by the ``family`` key of a
configuration file: ``to_config`` maps the file's keys onto the program's
configuration object, ``model_dims`` gives the sizes the flops/bytes table
needs, ``reference_fn`` is the family's plain float32 reference."""
