"""Plain references of the benchmark's deployments, independent of the engine.

One module per kind of deployment, named by a configuration's ``reference``
key. Each takes plain arrays of live agents and the configuration's own
values, and imports nothing of the program.
"""
