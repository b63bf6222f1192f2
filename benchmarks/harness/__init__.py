"""The benchmark's own machinery: files found by name, the device line,
the measured window, the trace reducer, the table of peaks."""
