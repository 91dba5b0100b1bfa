"""Operations and bytes each architecture's served programs need, from
shapes alone."""
