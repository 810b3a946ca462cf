"""Entry drivers: one module per entry point of the program that a cell can
drive. A driver offers ``prepare(cell)``, ``run(cell, outdir, telemetry)``,
``check(cell, control=None)``, ``telemetry_files(step)``,
``fallbacks(cell)`` and ``work(cell)``; ``README.md`` says what each is."""
