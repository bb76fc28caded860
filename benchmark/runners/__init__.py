"""One module per kind of cell, found by the ``runner`` key of the cell's traffic mix.

A runner gives ``setup(run) -> state``, ``window(run, state)``,
``end_to_end(run, state) -> {metric: value}``, ``check(run, state) ->
[Comparison]`` and ``teardown(state)``; ``benchmark/run.py`` does the rest.
"""
