"""Operations and bytes of the program's kernels, and the peaks they are held to."""
