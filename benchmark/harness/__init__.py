"""The benchmark's harness: drivers, readers, trace reduction, peaks,
the plain reference and the comparison that decides ``correct``.

Everything here is the yardstick and lives under ``benchmark/``; from
the program (``ndstpu``) it takes only the system under test (Session,
the serve daemon and its client, the data generator's raw files) and its
spans, counters and device-op names.
"""
