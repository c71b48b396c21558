select i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
       d_moy, s_store_id, sumsales, rk
from (select i_category, i_class, i_brand, i_product_name, d_year,
             d_qoy, d_moy, s_store_id,
             sum(coalesce(ss_sales_price * ss_quantity, 0)) as sumsales,
             rank() over (partition by i_category
               order by sum(coalesce(ss_sales_price * ss_quantity, 0)) desc
             ) as rk
      from store_sales, date_dim, store, item
      where ss_sold_date_sk = d_date_sk
        and ss_item_sk = i_item_sk
        and ss_store_sk = s_store_sk
        and d_month_seq between {dms} and {dms_last}
      group by rollup(i_category, i_class, i_brand, i_product_name,
                      d_year, d_qoy, d_moy, s_store_id)) dw
where rk <= 100
order by i_category nulls last, i_class nulls last, i_brand nulls last,
         i_product_name nulls last, d_year nulls last, d_qoy nulls last,
         d_moy nulls last, s_store_id nulls last, sumsales, rk
limit 100
