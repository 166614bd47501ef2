"""The yardstick of benchmarks/chip: service driver, traffic generator,
statistics, trace reduction, peaks, comparison. Copied from chip_smoke.py
where that was sound (Service, build_executor, compare_text), so that a
later PR can change the program and its smoke but not what it is measured by."""
