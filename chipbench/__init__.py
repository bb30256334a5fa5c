"""The chip benchmark of horovod_tpu: one cell, one run, one process.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` — see ``chipbench/README.md``. Everything that decides a
number lives here (traffic, shape functions, peaks, the trace reduction,
the plain references and the comparison behind ``correct``); from the
program the benchmark takes only the system under test.
"""
