"""The benchmark's own library: device checks, traffic, the serving window,
the helpers of the work counts and of the plain references (each
architecture's own are in bench/arch), trace reduction. Nothing
here is imported by the program; the program is imported only to build and
drive the engine under test."""
