"""chipbench — ray_tpu's chip benchmark (see README.md beside this file).

Everything that decides a number lives in this directory: traffic
generation, trace reduction, the table of peaks, the operation counts and
the plain float32 references. From the program it takes only the system
under test (JaxTrainer, Serve + llm, Dataset.map_batches).
"""
