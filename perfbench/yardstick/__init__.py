"""The benchmark's own yardstick: what measures, and is never the system
under test. Nothing in this package imports ``tony_tpu``; the job scripts
under ``jobs/`` are the only files that call into the program."""
