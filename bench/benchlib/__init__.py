"""The benchmark's own library: device checks, traffic, the serving window,
operation and byte counts, trace reduction and the plain reference. Nothing
here is imported by the program; the program is imported only to build and
drive the engine under test."""
