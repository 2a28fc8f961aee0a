"""Operations and bytes of one kernel call, from the layer's mathematics."""
