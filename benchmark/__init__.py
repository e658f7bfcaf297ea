"""The benchmark of record for horovod_tpu (BENCHMARK.json at the root).

Everything that decides a number lives here, where a later PR cannot
change it: traffic generation, the plain reference, the FLOPs and bytes
arithmetic, the table of peaks, the reduction from the profiler's trace
and the comparison that decides ``correct``. From the program it takes
the system under test (``hvd.init``, the loader and prefetcher,
``build_train_step``) and nothing else. PERF.md says how to add a cell,
a configuration, a traffic mix or a per-layer metric as new files."""
