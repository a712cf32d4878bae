# Submodules are imported explicitly (``from repro.distributed import
# sharding``): the package itself imports nothing, so the storage workers
# (``workers``, which pulls in multiprocessing/socket machinery every
# in-process engine path stays free of) start without JAX.
